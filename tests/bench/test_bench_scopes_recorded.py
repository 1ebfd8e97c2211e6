"""``bench/scope_reduce.py`` and the trace reduction on a serve trace
recorded on the chip with the program's named scopes and host spans (see
the file's ``source``; ``scripts/record_serve_trace.py`` makes it)."""

import gzip
import json
from types import SimpleNamespace as NS

import pytest

from _bench_fixtures import ROOT
from bench import scope_reduce as sr
from bench import trace_reduce, work

DATA = ROOT / "tests/bench/data/serve_scopes_v5e.json.gz"
WIDTHS = work.Widths(layers=2, d_model=2560, heads=32, kv_heads=8, head_dim=128,
                     d_ff=9728, vocab=151936)


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(gzip.decompress(DATA.read_bytes()))
    v = d["values"]

    def event(e):
        stats = e[3] if len(e) > 3 else []
        return NS(name=v[e[0]], start_ns=e[1], duration_ns=e[2],
                  stats=[(v[k], v[x]) for k, x in zip(stats[::2], stats[1::2])])

    planes = [NS(name=p["name"], lines=[NS(name=ln["name"],
                                           events=[event(e) for e in ln["events"]])
                                        for ln in p["lines"]]) for p in d["planes"]]
    return NS(pd=NS(planes=planes), anchor=d["anchor"], names=d["op_names"],
              spans=[tuple(s) for s in d["spans"]], events=[tuple(e) for e in d["events"]])


def _window(rec):
    """The extent of the recorded ops, on the trace's clock: a program
    call cut at either end of the recording does not lie wholly inside."""
    ev = [e for p in rec.pd.planes if p.name.startswith("/device")
          for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    return min(e.start_ns for e in ev), max(e.start_ns + e.duration_ns for e in ev)


def test_every_scope_of_both_steps_is_found(recorded):
    t = sr.scope_times(recorded.pd, *_window(recorded), recorded.names)
    assert t.calls["decode"] >= 3 and t.calls["prefill"] >= 1
    for kind, scopes in {
        "decode": ("weights", "kv_pool.read", "kv_pool.write", "kv.append",
                   "attn.kv_gather", "attn.core"),
        "prefill": ("weights", "attn.core", "kv.scatter"),
    }.items():
        found = set(t.seconds[kind])
        for s in scopes:
            assert t.per_call_ms(kind, s) > 0, (kind, s)
        for proj in sr.PROJECTIONS:
            assert any(k.startswith(proj + "/") and k.split("/")[1] in sr.PATHS
                       for k in found), (kind, proj)
    # at two layers the tied head's embedding copy (XLA's layout copy,
    # named for the parameter) is a third of the decode step; no other
    # device time goes unscoped (at 36 layers, 8% in all: PERF.md §5)
    assert t.per_call_ms("decode", sr.UNSCOPED) < 0.4 * t.per_call_ms("decode")


def test_spans_share_the_trace_clock(recorded):
    a_ns = sr.anchor_ns(recorded.pd)
    spans = sr.to_trace_clock(recorded.spans, recorded.anchor, a_ns)
    offsets = sr.clock_offsets_us(spans, sr.host_annotations(recorded.pd))
    assert len(offsets) >= 10
    assert max(offsets) < 50.0


def test_long_idle_gaps_carry_a_span(recorded):
    a_ns = sr.anchor_ns(recorded.pd)
    spans = sr.to_trace_clock(recorded.spans, recorded.anchor, a_ns)
    t0, t1 = _window(recorded)
    gaps = sr.idle_gaps(recorded.pd, t0, t1, spans)
    long = [g for g in gaps if g[1] > 100e-6]
    assert long and all(label.startswith("serve.") for label, _ in long)
    # the same from the host plane's own annotations
    assert [g[0] for g in sr.idle_gaps(recorded.pd, t0, t1,
                                       sr.host_annotations(recorded.pd))
            if g[1] > 100e-6] == [label for label, _ in long]


def test_host_stall_share_of_the_recorded_run(recorded):
    ev = recorded.events
    share = sr.host_stall_share(recorded.spans, ev, ev[0][2], ev[-1][2])
    assert 0 < share < 100


def test_trace_reduce_still_finds_every_projection(recorded):
    """The scoped program's trace reduces as before: every projection of
    every call found by shape."""
    a_ns = sr.anchor_ns(recorded.pd)
    t0, t1 = _window(recorded)
    at = lambda ns: recorded.anchor + (ns - a_ns) * 1e-9  # noqa: E731
    win = NS(t0=at(t0), t1=at(t1), steps=(0, 0))
    r = trace_reduce.reduce(recorded.pd, recorded.anchor, win, WIDTHS,
                            NS(bucket_per_step=[]), None)
    assert r.gemm_missing == 0 and r.calls
    assert 0 <= r.non_gemm_ms("decode") < r.program_ms("decode")
