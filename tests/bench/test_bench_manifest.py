"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name in
it resolves to a file."""

import json
import math
import re

import pytest

from _bench_fixtures import ROOT

M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert M["paths"] == ["bench", "tests/bench"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_entries_have_just_their_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_names_units_and_text():
    names = ([c["name"] for c in M["configs"]] + CELLS
             + [m["name"] for m in M["end_to_end"] + M["per_layer"]]
             + [w["traffic"] for w in M["workloads"]]
             + [k for c in M["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({e["name"] for e in M[kind]}) == len(M[kind])
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    texts = ([w["why"] for w in M["workloads"]] + [c["why"] for c in M["configs"]]
             + [c["source"] for c in M["configs"]] + [m["layer"] for m in M["per_layer"]]
             + M["command"])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t for t in texts)


def test_bounds_and_budget():
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


def test_every_config_has_a_cell_and_a_file():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert (ROOT / "bench/references" / f"{data['reference']}.py").is_file()
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    w = next(x for x in M["workloads"] if x["name"] == cell)
    assert w["chips"] in (1, 4)
    assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
    params = json.loads((ROOT / "bench/cells" / f"{cell}.json").read_text())
    assert params["slots"] >= 1 and params["check_tokens"] >= 1
    assert params["limits"]["max_logit_gap"] > 0
    e2e = {m["name"] for m in M["end_to_end"] if cell in m.get("workloads", [cell])}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in M["per_layer"]
             if cell in m.get("workloads", [cell] if m["moves"] in e2e else [])]
    assert layer
    for m in layer:
        assert m["moves"] in e2e  # what it moves is reported in the same cell
    for m in M["end_to_end"] + M["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_layer_names_agree():
    layers = {}
    for m in M["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    assert {m["layer"] for m in M["per_layer"]} == {
        "scheduler", "model step", "attention and KV cache", "flex GEMM kernels", "device"}


def test_shares_of_a_peak_are_named_for_it():
    for m in M["per_layer"]:
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
    assert any("mfu" in m["name"] for m in M["per_layer"])


@pytest.mark.parametrize("config", sorted(p.stem for p in (ROOT / "bench/configs").glob("*.json")))
def test_program_config_is_the_published_one(config):
    """The program's preset with the file's overrides has every published
    width, for every configuration file (one may wait for its cells); the
    harness refuses a run otherwise."""
    from bench import harness
    from repro.launch import serve

    data = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    prog = data["program"]
    cfg = serve.serve_config(serve.parse_args(["--arch", prog["arch"], *prog["flags"]]))
    harness.published_widths_match(cfg.replace(**prog["overrides"]), data)
    assert cfg.use_pallas and not cfg.attn_pallas
    with pytest.raises(SystemExit):
        harness.published_widths_match(cfg.replace(d_model=cfg.d_model + 1), data)
    assert not math.isnan(cfg.rope_theta)
