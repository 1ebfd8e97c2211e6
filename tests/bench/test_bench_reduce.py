"""The reduction from the scheduler's counters and a device trace to the
window and the per-layer numbers, on a small recorded trace."""

import gzip
import json
from types import SimpleNamespace as NS

import pytest

from _bench_fixtures import ROOT
from bench import trace_reduce, window, work

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _stats_and_results():
    """Two slots; r0 (5 prompt tokens, 3 new), r1 (6, 2) and r2 (7, 2), all
    queued at once, as ``ServeScheduler.run`` would count them."""
    stats = NS(events=[(0, 0, 0.0), (0, 2, 1.0), (1, 4, 2.0), (1, 5, 3.0),
                       (2, 7, 4.0), (2, 7, 4.1)],
               active_per_step=[2, 2], bucket_per_step=[2, 2])
    reqs = [NS(rid=0, prompt=[0] * 5, max_new=3), NS(rid=1, prompt=[0] * 6, max_new=2),
            NS(rid=2, prompt=[0] * 7, max_new=2)]
    results = {0: NS(admitted_step=0, finished_step=2),
               1: NS(admitted_step=0, finished_step=1),
               2: NS(admitted_step=1, finished_step=2)}
    return stats, results, reqs


def test_window_starts_full_and_ends_when_the_queue_empties():
    stats, results, reqs = _stats_and_results()
    w = window.select(stats, results, reqs, capacity=2, seconds=10.0)
    assert (w.t0, w.t1, w.steps, w.admitted, w.tokens) == (1.0, 3.0, (0, 1), (2, 3), 3)
    assert w.prefilled() == (2,) and w.seconds == 2.0
    assert window.cached_per_step(w, stats, results, reqs) == [[5, 6]]


def test_window_ends_at_the_first_event_past_its_length():
    stats, results, reqs = _stats_and_results()
    w = window.select(stats, results, reqs, capacity=2, seconds=0.5)
    assert (w.t0, w.t1, w.steps, w.admitted, w.tokens) == (1.0, 2.0, (0, 1), (2, 2), 2)
    assert w.prefills == 0


def _recorded():
    """One TPU v5e, qwen3_4b at its widths cut to 2 layers, 4 slots (see
    the file's ``source``)."""
    d = json.loads(gzip.decompress(
        (ROOT / "tests/bench/data/serve_trace_v5e.json.gz").read_bytes()))
    names = d["names"]
    planes = [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=names[i], start_ns=s, duration_ns=t)
                                    for i, s, t in ln["events"]])
        for ln in p["lines"]]) for p in d["planes"]]
    return NS(planes=planes)


WIDTHS = work.Widths(layers=2, d_model=2560, heads=32, kv_heads=8, head_dim=128,
                     d_ff=9728, vocab=151936)


def _reduce(peaks=PEAKS):
    anchor, anchor_ns = 100.0, 43_252_318
    at = lambda ns: anchor + (ns - anchor_ns) * 1e-9  # noqa: E731
    win = NS(t0=at(45_000_000), t1=at(109_500_000), steps=(0, 3))
    stats = NS(bucket_per_step=[4, 4, 4])
    return trace_reduce.reduce(_recorded(), anchor, win, WIDTHS, stats, peaks)


def test_calls_and_projections_are_found():
    r = _reduce()
    assert [c.kind for c in r.calls] == ["prefill"] * 4 + ["decode"] * 3
    assert r.gemm_missing == 0
    assert all(c.gemms == 2 * 7 + 1 for c in r.calls)
    assert [c.rows for c in r.calls if c.kind == "decode"] == [4, 4, 4]
    assert r.window_s == pytest.approx(0.0645)
    assert 0 < r.busy_s <= r.window_s


def test_per_layer_numbers():
    r = _reduce()
    assert 7.2 < r.program_ms("decode") < 7.4
    assert 0 <= r.non_gemm_ms("decode") < r.program_ms("decode")
    for kind in ("prefill", "decode"):
        share = r.gemm_roofline(kind, PEAKS)
        assert 0 < share <= 100
    # no peaks, no roofline: the share reads 0 and the reader leaves it out
    assert _reduce(peaks=None).gemm_roofline("decode", None) == 0


def test_breakdown():
    b = _reduce().breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    ops = [k for k, _ in b["device_ops"]]
    assert "decode: gemm lm_head" in ops and "decode: weight lm_head" in ops
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
    labels = {k for k, _ in b["idle_gaps"]}
    assert labels <= {"before admission/prefill", "before decode step",
                      "before scheduler op", "end of window"}


def test_classifier_by_shape():
    c = trace_reduce.Classifier(WIDTHS)
    k = c('%flex_linear.7 = bf16[16,4096]{1,0:T(8,128)(2,1)} custom-call(bf16[16,2560]{1,0} '
          '%x, bf16[2560,4096]{1,0} %w), custom_call_target="tpu_custom_call"')
    assert k.gemm == ("attn.wq", 16, 2560, 4096)
    w = c('%copy.3 = bf16[152064,2560]{1,0:T(8,128)(2,1)} copy(bf16[152064,2560]{0,1} %e)')
    assert w.weight == "lm_head" and w.gemm is None
    s = c('%slice-start = ((bf16[36,2560,9728]{2,1,0}), bf16[1,2560,9728]{2,1,0:S(1)}, '
          's32[]{:S(2)}) async-start(bf16[36,2560,9728]{2,1,0} %p), calls=%a')
    assert s.weight == "mlp.w1"
    o = c('%fusion.1 = f32[8,4,128]{2,1,0} fusion(f32[8,4,128]{2,1,0} %a), kind=kLoop')
    assert o.gemm is None and o.weight is None and not o.container
    assert c('%while.2 = (s32[]) while((s32[]) %t), condition=%c, body=%b').container


def test_tracer_stops_itself_and_leaves_an_anchor():
    import time

    import jax
    import jax.numpy as jnp

    from bench import harness

    t = harness.Tracer(0.2)
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    deadline = time.perf_counter() + 5.0
    while t.t_stop is None and time.perf_counter() < deadline:
        f(x).block_until_ready()
    t.close()
    assert t.anchor < t.t_stop < deadline
    pd = trace_reduce.parse(t.xspace)
    names = {ev.name for p in pd.planes if p.name.startswith("/host")
             for ln in p.lines for ev in ln.events}
    assert "bench.anchor" in names
