"""The harness end to end on the CPU, at a tiny size, with the look for a
chip skipped: the run is correct, a cell, configuration, mix and metric
added as files are found by name, and a timed path broken underneath
makes ``correct`` false.  ``bench/run.py`` itself refuses to run without a
TPU."""

import hashlib
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from _bench_fixtures import ROOT


def run_tiny(root, program=None, seed=5):
    from bench import harness

    return harness.run("tiny.mini", seed, 1.0, False, root=root,
                       t_start=time.perf_counter(), require_tpu=False,
                       program=program)


def test_tiny_cell_is_correct_and_found_by_name(tiny_root, capsys):
    from bench import harness

    r = run_tiny(tiny_root)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 2
    assert set(r["metrics"]) == {"prompt_tokens_per_s", "output_tokens_per_s",
                                 "setup_s", "served_requests"}
    assert r["metrics"]["served_requests"]["value"] == r["attempted"]
    assert r["run"]["compiles_in_window"] == 0
    harness.emit(r)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert err.strip().splitlines()[-1].startswith("check ")


def test_added_files_leave_existing_ones_alone(bench_copy):
    from _bench_fixtures import TINY_OVERRIDES, TINY_WIDTHS, add_cell

    from bench import harness

    def digests():
        return {p: hashlib.sha256(p.read_bytes()).hexdigest()
                for p in (bench_copy / "bench").rglob("*") if p.is_file()}

    before = digests()
    add_cell(bench_copy, "tiny.other", "tiny", TINY_WIDTHS, TINY_OVERRIDES,
             {"arrival": {"kind": "all_at_start"},
              "prompt_len": {"kind": "fixed", "value": 16},
              "output_len": {"kind": "fixed", "value": 3},
              "block_size": 16},
             {"slots": 2, "check_tokens": 4, "limits": {"max_logit_gap": 0.05}})
    (bench_copy / "bench/metrics/dummy_layer.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    m = json.loads((bench_copy / "BENCHMARK.json").read_text())
    m["per_layer"].append({"name": "dummy_layer", "unit": "%", "better": "higher",
                           "source": "program_counter", "layer": "scheduler",
                           "moves": "prompt_tokens_per_s", "workloads": ["tiny.other"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(m))
    after = digests()
    assert all(after[p] == d for p, d in before.items())
    cell = harness.load_cell(bench_copy, "tiny.other")
    assert cell.mix.sizes(1, 3) == [(16, 3)] * 3
    assert [x["name"] for x in cell.per_layer] == ["dummy_layer"]
    assert harness.load_reader(bench_copy, "dummy_layer")(None) == 1.0


def test_committed_plan_is_the_one_served(tiny_root):
    """A plan committed under ``bench/plans/`` is what every checkout
    serves under: the run copies it and tunes nothing."""
    import shutil

    from bench import harness

    state = tiny_root / harness.STATE / "plans" / "tiny.mini.json"
    harness.Program(tiny_root, harness.load_cell(tiny_root, "tiny.mini"))
    committed = tiny_root / "bench" / "plans" / "tiny.mini.json"
    committed.parent.mkdir(exist_ok=True)
    shutil.move(state, committed)
    harness.Program(tiny_root, harness.load_cell(tiny_root, "tiny.mini"))
    assert state.read_bytes() == committed.read_bytes()


def _broken_decode(fault):
    def program(sched):
        decode = sched._decode

        def broken(params, pool_k, pool_v, table, positions, token, poison):
            tok, ok, pk, pv = decode(params, jnp.copy(pool_k), jnp.copy(pool_v),
                                     table, positions, token, poison)
            if fault == "token":      # a token altered where it is produced
                return (tok + 1) % 256, ok, pk, pv
            if fault == "state":      # the step returns its state unchanged
                return tok, ok, pool_k, pool_v
            half = tok.shape[0] // 2  # half the batch left out
            return tok.at[half:].set(token[half:]), ok, pk, pv
        sched._decode = broken
    return program


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])
def test_broken_timed_path_is_not_correct(tiny_root, fault):
    r = run_tiny(tiny_root, program=_broken_decode(fault))
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > r["checks"]["max_logit_gap"]["limit"]


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3_4b.prefill_heavy",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_run_exits_nonzero_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths has no program to run."""
    import shutil

    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in m["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
