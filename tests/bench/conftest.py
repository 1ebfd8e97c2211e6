"""Fixtures for the benchmark's CPU tests: the repo root on ``sys.path``
(``bench`` is imported as a package), and copies of the benchmark with a
tiny cell of their own."""

import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_fixtures import ROOT, TINY_OVERRIDES, TINY_WIDTHS, add_cell  # noqa: E402

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def bench_copy(tmp_path) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/``, with the program's
    ``src`` linked beside them."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path


@pytest.fixture
def tiny_root(bench_copy) -> Path:
    add_cell(bench_copy, "tiny.mini", "tiny", TINY_WIDTHS, TINY_OVERRIDES,
             {"arrival": {"kind": "all_at_start"},
              "prompt_len": {"kind": "lognormal", "median": 20, "sigma": 0.5,
                             "min": 8, "max": 48},
              "output_len": {"kind": "uniform", "min": 2, "max": 6},
              "block_size": 16},
             {"slots": 2, "check_tokens": 12, "limits": {"max_logit_gap": 0.05}},
             {"served_requests": "def read(ctx):\n    return float(len(ctx.requests))\n"})
    return bench_copy
