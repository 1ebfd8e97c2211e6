"""The reduction from named scopes and host spans to per-layer numbers
(``bench/scope_reduce.py``), and the host-stall readers, on synthetic
events and spans."""

from types import SimpleNamespace as NS

import pytest

from _bench_fixtures import ROOT
from bench import harness, scope_reduce as sr


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


JIT = "jit(decode_fn)/while/body/closed_call/"
# what as_text() prints for the ops of _synthetic(), op_names included
HLO = {
    "prefill": """HloModule jit_prefill_fn
ENTRY %main.1 (p: f32[2]) -> f32[2] {
  %a = f32[2]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(prefill_fn)/while/body/attn.core/dot_general"}
  ROOT %b = f32[2]{0} fusion(%a), metadata={op_name="jit(prefill_fn)/kv.scatter/scatter" stack_frame_id=3}
}
""",
    "decode": f"""HloModule jit_decode_fn
%async_computation.1 (param_0: f32[4]) -> f32[2] {{
  %param_0 = f32[4]{{0}} parameter(0)
  ROOT %dynamic-slice.2 = f32[2]{{0}} dynamic-slice(%param_0), metadata={{op_name="{JIT}kv_pool.read/dynamic_slice"}}
}}

ENTRY %main.2 (p: f32[4]) -> f32[2] {{
  %c-start = ((f32[4]{{0}}), f32[2]{{0}}, s32[]) async-start(%p), calls=%async_computation.1
  %c = f32[2]{{0}} async-done(%c-start)
  %d = f32[2]{{0}} custom-call(%c), custom_call_target="tpu_custom_call", metadata={{op_name="{JIT}mlp.w1/jit(flex_linear)/os/pallas_call"}}
  %copy-start = (f32[2]{{0}}, f32[2]{{0}}, u32[]) copy-start(%d)
  ROOT %e = f32[2]{{0}} copy-done(%copy-start)
}}
""",
}


def _synthetic():
    """A prefill (100-200 ns) and a decode step (300-400 ns), with the
    host's anchor, one admission, one sync and one decode dispatch."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_prefill_fn(1)", 100, 100),
                                       _ev("jit_decode_fn(2)", 300, 100)]),
        NS(name="XLA Ops", events=[
            _ev("%a = f32[2]{0} fusion(f32[2]{0} %x), kind=kLoop", 100, 40),
            _ev("%b = f32[2]{0} fusion(f32[2]{0} %a)", 150, 40),
            _ev("%while.1 = (s32[]) while((s32[]) %t), body=%b", 300, 100),
            _ev("%c = f32[2]{0} async-done(((f32[4]{0}), f32[2]{0}, s32[]) %c-start)",
                300, 30),
            _ev('%d = f32[2]{0} custom-call(f32[2]{0} %c), custom_call_target="tpu_custom_call"',
                340, 30),
            _ev("%f = f32[2]{0} fusion(f32[2]{0} %x)", 380, 20)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.anchor", 0, 1),
        _ev("serve.admit", 50, 60, step=0, rid=3),
        _ev("serve.sync", 200, 90, step=0, rid=-1),
        _ev("serve.decode", 290, 12, step=0, rid=-1)])])
    return NS(planes=[device, host])


def _names():
    return {kind: sr.op_names(text) for kind, text in HLO.items()}


def test_op_names_from_compiled_text():
    names = _names()
    assert names["prefill"] == {
        "%a = f32[2]{0}": "jit(prefill_fn)/while/body/attn.core/dot_general",
        "%b = f32[2]{0}": "jit(prefill_fn)/kv.scatter/scatter"}
    d = names["decode"]
    # compiler-added async and copy ops take the scope of what they move
    assert sr.scope_of(d["%c = f32[2]{0}"]) == "kv_pool.read"
    assert sr.scope_of(d["%c-start = ((f32[4]{0}), f32[2]{0}, s32[])"]) == "kv_pool.read"
    assert sr.scope_of(d["%e = f32[2]{0}"]) == "mlp.w1/os"
    # a trace event's name and an as_text() line give one key
    assert sr.instruction_key(
        "%c = f32[2]{0} async-done(((f32[4]{0}), f32[2]{0}, s32[]) %c-start)") in d


@pytest.mark.parametrize("op_name, scope", [
    ("jit(decode_fn)/while/body/closed_call/mlp.w1/jit(flex_linear)/ws/pallas_call",
     "mlp.w1/ws"),
    ("jit(prefill_fn)/lm_head/xla/...d,df->...f/dot_general", "lm_head/xla"),
    ("jit(decode_fn)/while/body/closed_call/kv.append/scatter", "kv.append"),
    ("jit(decode_fn)/while/body/dynamic_slice", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of(op_name, scope):
    assert sr.scope_of(op_name) == scope


def test_scope_times_per_call():
    t = sr.scope_times(_synthetic(), 0, 1000, _names())
    assert t.calls == {"prefill": 1, "decode": 1}
    assert t.seconds["decode"] == pytest.approx(
        {"kv_pool.read": 30e-9, "mlp.w1/os": 30e-9, "unscoped": 20e-9})
    assert t.per_call_ms("prefill", "attn.core") == pytest.approx(40e-6)
    assert t.per_call_ms("decode", "kv_pool.") == pytest.approx(30e-6)
    assert t.per_call_ms("decode") == pytest.approx(80e-6)  # the container left out
    assert t.per_call_ms("decode", "attn.kv_gather") is None
    # a call that does not lie wholly inside the interval is not counted
    assert sr.scope_times(_synthetic(), 0, 350, _names()).calls == {"prefill": 1}
    # with no op_names every op is unscoped
    assert set(sr.scope_times(_synthetic(), 0, 1000, {}).seconds["decode"]) == {"unscoped"}


def test_idle_gaps_carry_the_host_span_over_them():
    pd = _synthetic()
    spans = sr.host_annotations(pd)
    assert [s[0] for s in spans] == ["serve.admit", "serve.sync", "serve.decode"]
    assert sr.anchor_ns(pd) == 0
    labelled = dict(sr.idle_gaps(pd, 0, 400, spans))
    assert labelled["serve.admit > admission/prefill"] == pytest.approx(100e-9)
    assert labelled["serve.sync > decode step"] == pytest.approx(110e-9)
    # under serve.run, a gap its children cover less of than its own
    # time is the scheduler's bookkeeping
    late = [("serve.admit", 80, 110, 0, 3), ("serve.run", 0, 1000, -1, -1)] + spans[1:]
    rooted = dict(sr.idle_gaps(pd, 0, 400, late))
    assert rooted["serve.run > admission/prefill"] == pytest.approx(100e-9)
    assert rooted["serve.sync > decode step"] == pytest.approx(110e-9)
    # without spans, the labels trace_reduce gives
    plain = [label for label, _ in sr.idle_gaps(pd, 0, 400)]
    assert plain[:3] == ["before admission/prefill", "before decode step",
                         "before decode step"]


def test_spans_on_the_trace_clock_meet_their_annotations():
    pd = _synthetic()
    anchor = 5.0  # perf_counter inside bench.anchor, which starts at 0 ns
    spans = [("serve.admit", anchor + 50e-9, anchor + 110e-9, 0, 3),
             ("serve.sync", anchor + 201e-9, anchor + 290e-9, 0, -1),
             ("serve.run", anchor, anchor + 1.0, -1, -1)]
    on_trace = sr.to_trace_clock(spans, anchor, sr.anchor_ns(pd))
    assert on_trace[1][1] == pytest.approx(50)
    assert sr.clock_offsets_us(on_trace, sr.host_annotations(pd)) == pytest.approx(
        [0.0, 1e-3], abs=1e-6)


def _spans_and_events():
    """Syncs ending at 0.2, 0.4 and 0.7 s; an admission after the first
    (its end 0.1 s after the sync), a decode after the second (0.15 s)."""
    spans = [("serve.run", 0.0, 1.0, -1, -1), ("serve.sync", 0.1, 0.2, 0, -1),
             ("serve.admit", 0.25, 0.3, 0, 5), ("serve.sync", 0.3, 0.4, 0, -1),
             ("serve.decode", 0.5, 0.55, 0, -1), ("serve.sync", 0.6, 0.7, 1, -1),
             ("serve.decode", 0.72, 0.75, 1, -1)]
    events = [(0, 0, 0.2), (0, 1, 0.4), (1, 2, 0.7)]
    return spans, events


def test_host_stall_share():
    spans, events = _spans_and_events()
    # the window's events are the first two; the third ends it
    assert sr.host_stall_share(spans, events, 0.2, 0.7) == pytest.approx(50.0)
    assert sr.host_stall_share(spans, events, 0.4, 0.7) == pytest.approx(50.0)
    # a sync followed by another sync before any work adds nothing
    doubled = spans[:2] + [("serve.sync", 0.21, 0.22, 0, -1)] + spans[2:]
    events3 = events[:1] + [(0, 0, 0.22)] + events[1:]
    assert sr.host_stall_share(doubled, events3, 0.2, 0.7) == pytest.approx(
        100 * (0.3 - 0.22 + 0.15) / 0.5)
    assert sr.host_stall_share([], [], 0.0, 1.0) is None


@pytest.mark.parametrize("metric", ["host_stall_share.prefill", "host_stall_share.decode"])
def test_host_stall_readers(metric):
    read = harness.load_reader(ROOT, metric)
    spans, events = _spans_and_events()
    ctx = NS(stats=NS(spans=spans, events=events), win=NS(t0=0.2, t1=0.7))
    assert read(ctx) == pytest.approx(50.0)
    # a program that records no spans (the parent of this change) reads None
    assert read(NS(stats=NS(events=events), win=ctx.win)) is None
