"""The traffic generator and the work counters, on the CPU."""

import json
from collections import Counter

import pytest

from _bench_fixtures import ROOT
from bench import traffic, work

MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))


def mix(name):
    return traffic.Mix.load(ROOT / "bench/traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_deterministic_by_seed(name):
    m = mix(name)
    a = m.requests(2**31 + 17, 40, 151936)
    b = m.requests(2**31 + 17, 40, 151936)
    assert all((p == q).all() and g == h for (p, g), (q, h) in zip(a, b))
    c = m.requests(2**31 + 18, 40, 151936)
    assert any(len(p) != len(q) or (p != q).any() for (p, _), (q, _) in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_the_same_set_for_every_seed(name):
    m = mix(name)
    k = traffic.STRATA
    for seed in (0, 1, 3_000_000_001):
        sizes = m.sizes(seed, 3 * k)
        for p, g in sizes:
            assert m.prompt.min <= p <= m.prompt.max
            assert m.output.min <= g <= m.output.max
            assert p + g <= m.max_total_len
        for b in range(3):  # each block of STRATA requests: the same sizes
            block = sizes[b * k:(b + 1) * k]
            assert Counter(p for p, _ in block) == Counter(m.prompt_lengths())
            assert Counter(g for _, g in block) == Counter(m.output_lengths())
    assert m.sizes(5, 7) == m.sizes(5, 30)[:7]  # a longer trace extends a shorter
    toks = m.requests(5, 4, 1000)
    assert all(0 <= t.min() and t.max() < 1000 for t, _ in toks)


def test_stratum_midpoints():
    d = traffic.Dist.from_json({"kind": "lognormal", "median": 1024, "sigma": 0.5,
                                "min": 512, "max": 2048})
    s = d.strata(16)
    assert s == sorted(s) and s[0] > 512 and s[-1] < 2048
    assert abs(s[7] - 1024) < 60 and abs(s[8] - 1024) < 60
    u = traffic.Dist.from_json({"kind": "uniform", "min": 2, "max": 16})
    assert u.strata(15) == list(range(2, 17))
    assert traffic.Dist.from_json({"kind": "fixed", "value": 7}).strata(3) == [7, 7, 7]


def widths(name):
    return work.Widths.from_config(
        json.loads((ROOT / f"bench/configs/{name}.json").read_text()))


# hand counts from the published widths
QWEN = dict(layer=2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728,
            head=2560 * 151936, kv=2 * 2 * 36 * 1024, attn=4 * 36 * 32 * 128, L=36, D=2560)
MINICPM = dict(layer=4 * 2304 * 2304 + 3 * 2304 * 5760, head=2304 * 122753,
               kv=2 * 2 * 40 * 2304, attn=4 * 40 * 36 * 64, L=40, D=2304)


@pytest.mark.parametrize("name,h", [("qwen3_4b", QWEN), ("minicpm_2b", MINICPM)])
def test_work_against_hand_counts(name, h):
    w = widths(name)
    assert h["layer"] == {"qwen3_4b": 100_925_440, "minicpm_2b": 61_046_784}[name]
    assert work.layer_weight_elems(w) == h["layer"]
    assert work.weight_bytes(w) == 2 * (h["L"] * h["layer"] + h["head"])
    assert work.kv_bytes_per_token(w) == h["kv"]
    assert work.attention_flops(w, 1) == h["attn"]
    p = 1000
    pf = work.prefill(w, p)
    assert pf.flops == 2 * p * h["L"] * h["layer"] + 2 * h["head"] + h["attn"] * p * (p + 1) // 2
    assert pf.bytes == 2 * (h["L"] * h["layer"] + h["head"]) + p * h["kv"] + 2 * p * h["D"]
    dc = work.decode_step(w, [10, 20])
    assert dc.flops == 2 * (2 * h["L"] * h["layer"] + 2 * h["head"]) + h["attn"] * (11 + 21)
    assert dc.bytes == (2 * (h["L"] * h["layer"] + h["head"]) + 30 * h["kv"]
                        + 2 * h["kv"] + 2 * 2 * h["D"])
    assert work.decode_step(w, []) == work.ZERO


def test_per_token_figures():
    q, m = widths("qwen3_4b"), widths("minicpm_2b")
    assert 2 * q.layers * work.layer_weight_elems(q) == 7_266_631_680
    assert 2 * m.layers * work.layer_weight_elems(m) == 4_883_742_720
    assert work.kv_bytes_per_token(q) == 147_456 and work.kv_bytes_per_token(m) == 368_640
    names = [n for n, _, _ in work.projections(q)]
    assert names == ["attn.wq", "attn.wk", "attn.wv", "attn.wo", "mlp.w1",
                     "mlp.w2", "mlp.w3", "lm_head"]


def test_roofline_and_peaks():
    peaks = work.load_peaks(ROOT / "bench/peaks.json", "TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks(ROOT / "bench/peaks.json", "TPU v9 imaginary")
    assert work.roofline_s(work.Work(197e12, 1.0), peaks) == pytest.approx(1.0)
    assert work.roofline_s(work.Work(1.0, 819e9), peaks) == pytest.approx(1.0)
    g = work.gemm(16, 2560, 4096)  # a decode projection is bound by its weight
    assert work.roofline_s(g, peaks) == pytest.approx(g.bytes / 819e9)
