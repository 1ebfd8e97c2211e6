"""From a device trace and the scheduler's host spans to device time per
named scope, and to what the host did in each idle gap.

The program names its device work with ``jax.named_scope``; each scope
lands in the HLO ``op_name`` metadata of the instructions it covers.  The
trace names each executed op by its instruction's text and carries no
``op_name`` (``ProfileData`` shows no such stat on a TPU), so the op_names
come from the compiled programs' ``as_text()`` (``op_names``), matched by
instruction name and result shape (``instruction_key``).  A scope is a
component of the op_name that the program names (``SCOPES``): the
projections, each with the path its kernel took (``is``/``os``/``ws`` or
``xla``), the layer's weight slabs, and the attention and KV-cache steps.
An op with no op_name, or whose op_name holds none of them, is
``unscoped``.

The scheduler records host spans (``ServeStats.spans``, ``serve.*``) on
``perf_counter``, each also a profiler annotation of the same name; the
annotation ``bench.anchor``, made at a known ``perf_counter`` reading,
puts both on the device's clock.

Everything here reads what ``trace_reduce.parse`` returns (planes, lines,
events with ``name``, ``start_ns``, ``duration_ns`` and ``stats``).
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass

from bench.trace_reduce import CONTAINER, NAMES, PROGRAMS, _union

PROJECTIONS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
               "mlp.w1", "mlp.w2", "mlp.w3", "lm_head")
PATHS = ("is", "os", "ws", "xla")
KV = ("kv_pool.read", "kv_pool.write", "kv.append", "attn.kv_gather",
      "attn.core", "kv.scatter")
# one layer's weight slabs, sliced out of the stacked weights by the scan
WEIGHTS = "weights"
SCOPES = frozenset(PROJECTIONS + PATHS + KV + (WEIGHTS,))
UNSCOPED = "unscoped"
SPAN_PREFIX = "serve."
# the scheduler's root span; its children do not overlap one another
ROOT_SPAN = "serve.run"


def scope_of(op_name: str | None) -> str:
    """The scope path an op ran under: its ``SCOPES`` components joined by
    ``/`` (``mlp.w1/os``, ``kv_pool.read``), or ``unscoped``."""
    if not op_name:
        return UNSCOPED
    parts = [p for p in op_name.split("/") if p in SCOPES]
    return "/".join(parts) if parts else UNSCOPED


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-_]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%[\w.\-]+")
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_HEADER = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")


def instruction_key(text: str) -> str:
    """An HLO instruction's name and result shape, the part of it that a
    trace event's name and an ``as_text()`` line print alike:
    ``%fusion.3 = bf16[48,8,128]{2,1,0:T(8,128)(2,1)}``."""
    lhs, _, rhs = text.strip().removeprefix("ROOT ").partition(" = ")
    m = _OPCODE.search(rhs)
    return f"{lhs} = {(rhs[:m.start()] if m else rhs).strip()}"


def op_names(*hlo_texts: str) -> dict[str, str]:
    """``instruction_key`` -> op_name for the instructions of compiled
    modules' ``as_text()`` (the first text wins a key).  An instruction the
    compiler added carries none: it takes that of the computation it calls
    (an async slice's), else that of its first operand (a copy's), so the
    copies the compiler puts around a scoped op count under its scope."""
    out: dict[str, str] = {}
    for text in hlo_texts:
        own: dict[str, str | None] = {}     # instruction -> its op_name
        first: dict[str, str | None] = {}   # -> called root or first operand
        roots: dict[str, str] = {}          # computation -> its root
        keys: dict[str, str] = {}
        comp = None
        for line in text.splitlines():
            head = _HEADER.match(line)
            if head:
                comp = head.group(1)
                continue
            body = line.strip()
            if not body.startswith(("%", "ROOT %")):
                continue
            text_part = body.split(", metadata=")[0]
            name = text_part.removeprefix("ROOT ").split(" = ")[0]
            keys[name] = instruction_key(text_part)
            m = _OP_NAME.search(body)
            own[name] = m.group(1) if m else None
            if body.startswith("ROOT") and comp is not None:
                roots[comp] = name
            called = _CALLS.search(text_part)
            op = _OPCODE.search(text_part.split(" = ", 1)[1])
            operand = _NAME.search(text_part, op.end()) if op else None
            first[name] = (roots.get(called.group(1)) if called
                           else operand.group(0) if operand else None)

        def resolve(name, depth=0):
            if own.get(name) or depth > 8 or first.get(name) is None:
                return own.get(name)
            return resolve(first[name], depth + 1)

        for name, key in keys.items():
            got = resolve(name)
            if got:
                out.setdefault(key, got)
    return out


def host_annotations(pd, prefix: str = SPAN_PREFIX) -> list[tuple]:
    """``(name, start_ns, end_ns, step, rid)`` of the host plane's
    annotations whose name starts with ``prefix``, in start order."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    st = dict(ev.stats)
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                int(st.get("step", -1)), int(st.get("rid", -1))))
    return sorted(out, key=lambda s: s[1])


def anchor_ns(pd, name: str = "bench.anchor") -> int:
    for a in host_annotations(pd, name):
        if a[0] == name:
            return a[1]
    raise ValueError(f"the trace has no {name} annotation")


def to_trace_clock(spans, anchor: float, anchor_at_ns: int) -> list[tuple]:
    """``ServeStats.spans`` (``perf_counter``) on the trace's clock."""
    return sorted(((n, anchor_at_ns + (t0 - anchor) * 1e9,
                    anchor_at_ns + (t1 - anchor) * 1e9, step, rid)
                   for n, t0, t1, step, rid in spans), key=lambda s: s[1])


def clock_offsets_us(spans, annotations) -> list[float]:
    """For each span (trace clock) that has a host annotation, the larger
    of the distances between their starts and between their ends, in µs.
    A span's annotation has its (name, step, rid) and overlaps it (spans of
    one key never overlap one another)."""
    by_key: dict[tuple, list] = defaultdict(list)
    for a in annotations:
        by_key[(a[0], a[3], a[4])].append(a)
    out = []
    for n, s, e, step, rid in spans:
        for a in by_key.get((n, step, rid), ()):
            if a[1] < e and s < a[2]:
                out.append(max(abs(s - a[1]), abs(e - a[2])) * 1e-3)
                break
    return out


@dataclass
class ScopeTimes:
    calls: dict[str, int]                   # program kind -> calls
    seconds: dict[str, dict[str, float]]    # kind -> scope -> device seconds

    def per_call_ms(self, kind: str, *prefixes: str) -> float | None:
        """Mean device ms per call of ``kind`` under the scopes that start
        with any of ``prefixes`` (every scope where none is given), or None
        where no call was traced or no op ran under them."""
        n = self.calls.get(kind, 0)
        got = [v for k, v in self.seconds.get(kind, {}).items()
               if not prefixes or k.startswith(prefixes)]
        if not n or not got:
            return None
        return 1e3 * sum(got) / n


def scope_times(pd, t0_ns: float, t1_ns: float, names: dict[str, dict[str, str]],
                device: str = "/device:TPU:0") -> ScopeTimes:
    """Device time per scope in each program call lying wholly inside
    ``[t0_ns, t1_ns]``: the ops of the ``XLA Ops`` line (the op stream;
    an asynchronous copy in flight is no op running), containers left
    out, as ``trace_reduce`` counts them.  ``names`` holds ``op_names``
    of the compiled programs of each kind (``PROGRAMS``)."""
    dev = next(p for p in pd.planes if p.name == device)
    lines = {ln.name: ln for ln in dev.lines}
    calls = []
    for ev in lines["XLA Modules"].events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        kind = next((k for k, p in PROGRAMS.items() if p.match(ev.name)), None)
        if kind is not None and t0_ns <= s and e <= t1_ns:
            calls.append((s, e, kind))
    calls.sort()
    seconds: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    ci = 0
    for ev in lines["XLA Ops"].events:
        s = ev.start_ns
        while ci < len(calls) and calls[ci][1] <= s:
            ci += 1
        if ci == len(calls):
            break
        if calls[ci][0] > s or CONTAINER.match(ev.name):
            continue
        kind = calls[ci][2]
        scope = scope_of(names.get(kind, {}).get(instruction_key(ev.name)))
        seconds[kind][scope] += ev.duration_ns * 1e-9
    counts: dict[str, int] = defaultdict(int)
    for _, _, kind in calls:
        counts[kind] += 1
    return ScopeTimes(dict(counts), {k: dict(v) for k, v in seconds.items()})


def idle_gaps(pd, t0_ns: float, t1_ns: float, spans=(),
              device: str = "/device:TPU:0") -> list[tuple[str, float]]:
    """``(label, seconds)`` of each gap between the device's ops in
    ``[t0_ns, t1_ns]``.  The label is ``<span> > <next program>``, where
    ``<span>`` is the host span (trace clock) that covers most of the gap:
    a child of ``serve.run``, or ``serve.run`` itself where its own time
    (bookkeeping) covers more than any child; with no span over the gap,
    ``before <next program>`` as ``trace_reduce`` labels it."""
    dev = next(p for p in pd.planes if p.name == device)
    lines = {ln.name: ln for ln in dev.lines}
    modules = sorted((ev.start_ns, next((k for k, p in PROGRAMS.items()
                                         if p.match(ev.name)), "other"))
                     for ev in lines["XLA Modules"].events)
    busy = []
    for ev in lines["XLA Ops"].events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e > t0_ns and s < t1_ns:
            busy.append((max(s, t0_ns), min(e, t1_ns)))
    edges = [(t0_ns, t0_ns)] + _union(busy) + [(t1_ns, t1_ns)]
    out = []
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b <= a:
            continue
        nxt = next((k for s, k in modules if s >= a), None)
        what = NAMES[nxt] if nxt else None
        cover = [(min(b, e) - max(a, s), n) for n, s, e, _, _ in spans if s < b and e > a]
        children = [c for c in cover if c[1] != ROOT_SPAN]
        root = sum(c[0] for c in cover if c[1] == ROOT_SPAN)
        if root:  # the root's own time: what its children leave of the gap
            children.append((root - sum(c[0] for c in children), ROOT_SPAN))
        best = max(children)[1] if children else None
        if best is None:
            label = f"before {what}" if what else "end of window"
        else:
            label = f"{best} > {what}" if what else f"{best} > end of window"
        out.append((label, (b - a) * 1e-9))
    return out


def host_stall_share(spans, events, t0: float, t1: float) -> float | None:
    """Host time the device waits for after each sync, as % of
    ``[t0, t1)`` (``perf_counter``): for each ``serve.sync`` whose event
    lies in the interval, the end of the first ``serve.admit`` or
    ``serve.decode`` after it, before the next sync, less the sync's end.
    The k-th sync span is the k-th event of ``ServeStats.events``.  None
    without spans."""
    spans = sorted(spans, key=lambda s: s[1])
    syncs = [s for s in spans if s[0] == "serve.sync"]
    if not syncs or len(syncs) != len(events) or t1 <= t0:
        return None
    work = [s for s in spans if s[0] in ("serve.admit", "serve.decode")]
    stall, wi = 0.0, 0
    for k, (sync, ev) in enumerate(zip(syncs, events)):
        while wi < len(work) and work[wi][1] < sync[2]:
            wi += 1
        if not t0 <= ev[2] < t1 or wi == len(work):
            continue
        nxt = syncs[k + 1][1] if k + 1 < len(syncs) else float("inf")
        if work[wi][1] < nxt:
            stall += work[wi][2] - sync[2]
    return 100.0 * stall / (t1 - t0)
