"""From a device trace to per-layer numbers.

The profiler's XSpace is read with ``jax.profiler.ProfileData``.
On a TPU the device plane ``/device:TPU:<n>`` has a line ``XLA Modules``
(one event per program call, named ``jit_<function>(<fingerprint>)``) and
lines ``XLA Ops`` and ``Async XLA Ops`` (one event per HLO instruction
executed, named by the instruction's text: ``%name = <result shape>
<opcode>(<operand shapes> %operand, ...)``).  Host and device events share
one clock; a host annotation made at a known ``perf_counter`` reading
places the scheduler's window on it.

The program gives its steps and kernels no stable names yet, so the
reduction keys on what the trace shows today:

- a program call is a prefill or a decode step by its module's function
  name (``PROGRAMS``); every other module is a scheduler-side op;
- a projection is found by shape, not by kernel name: a ``custom-call``
  whose operands include ``(M, K)`` and ``(K, N)`` with ``(K, N)`` a
  projection of the configuration (the output head's ``N`` padded up to
  256) implements that projection, and so does any other instruction whose
  result is one layer's slab of such a weight (a copy or slice that stages
  it, or the output head rebuilt from the embedding).  A projection whose
  events the reduction cannot find is left out of both sides of the
  roofline share, and ``gemm_missing`` counts them.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

from bench import work

PROGRAMS = {"prefill": re.compile(r"^jit_prefill_fn\("),
            "decode": re.compile(r"^jit_decode_fn\(")}
NAMES = {"prefill": "admission/prefill", "decode": "decode step",
         "other": "scheduler op"}
CONTAINER = re.compile(r"^%(while|conditional|call)\b")
SHAPE = re.compile(r"\b(pred|s8|u8|s32|u32|s64|bf16|f16|f32|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _dims(s: str) -> tuple[int, ...]:
    return tuple(int(d) for d in s.split(",") if d)


def _squeeze(d: tuple[int, ...]) -> tuple[int, ...]:
    while len(d) > 2 and d[0] == 1:
        d = d[1:]
    return d


@dataclass(frozen=True)
class OpInfo:
    label: str                # for the breakdown
    gemm: tuple | None        # (projection, M, K, N) of a GEMM kernel
    weight: str | None        # projection whose weight slab it produces
    container: bool


class Classifier:
    """Parses instruction texts once each (they repeat every layer)."""

    def __init__(self, widths: work.Widths):
        self.proj: dict[tuple[int, int], str] = {}
        for name, K, N in work.projections(widths):
            self.proj.setdefault((K, N), name)
        self.vocab = widths.vocab
        self.d_model = widths.d_model
        self.cache: dict[str, OpInfo] = {}

    def projection(self, K: int, N: int) -> str | None:
        if (K, N) in self.proj:
            return self.proj[(K, N)]
        if K == self.d_model and self.vocab <= N < self.vocab + 256:
            return "lm_head"
        return None

    def __call__(self, text: str) -> OpInfo:
        info = self.cache.get(text)
        if info is None:
            info = self.cache[text] = self._parse(text)
        return info

    def _parse(self, text: str) -> OpInfo:
        lhs, _, rhs = text.partition(" = ")
        base = re.sub(r"\.\d+$", "", lhs.strip().lstrip("%"))
        container = bool(CONTAINER.match(text))
        # the result shapes come before the opcode's operand list
        m = re.search(r"\s([a-z][a-z0-9\-_]*)\(", rhs)
        result_part = rhs[:m.start()] if m else rhs
        operand_part = rhs[m.end():] if m else ""
        opcode = m.group(1) if m else ""
        results = [_squeeze(_dims(d)) for _, d in SHAPE.findall(result_part)]
        label = f"{base} {result_part.split('{')[0].strip()}"
        gemm = None
        if opcode == "custom-call" and "tpu_custom_call" in rhs:
            ops = [_squeeze(_dims(d))
                   for _, d in SHAPE.findall(operand_part.split("custom_call_target")[0])]
            mats = [d for d in ops if len(d) == 2]
            for a, b in zip(mats, mats[1:]):
                name = self.projection(a[1], b[1]) if a[1] == b[0] else None
                if name is not None:
                    gemm = (name, a[0], a[1], b[1])
                    label = f"gemm {name}"
                    break
        weight = None
        if gemm is None:
            for d in results:
                if len(d) != 2:
                    continue
                # a projection's weight slab, or the output head's weight in
                # the embedding's (vocab, d_model) layout
                weight = self.projection(*d) or (
                    "lm_head" if self.projection(d[1], d[0]) == "lm_head" else None)
                if weight is not None:
                    label = f"weight {weight}"
                    break
        return OpInfo(label, gemm, weight, container)


@dataclass
class Call:
    kind: str
    start: int
    end: int
    rows: int | None = None        # the bucket of a decode step
    gemm_roofline_s: float = 0.0
    gemms: int = 0
    spans: list = field(default_factory=list)  # projection events

    @property
    def gemm_s(self) -> float:
        """Time covered by the events that implement the projections (an
        asynchronous copy overlapping a kernel is counted once)."""
        return sum(e - s for s, e in _union(self.spans)) * 1e-9


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    calls: list[Call]
    ops: dict[str, float]                 # label -> seconds in the window
    gaps: list[tuple[str, float]]         # (what follows, seconds)
    gemm_missing: int = 0

    def _of(self, kind: str) -> list[Call]:
        return [c for c in self.calls if c.kind == kind]

    def program_ms(self, kind: str) -> float | None:
        calls = self._of(kind)
        if not calls:
            return None
        return 1e-6 * sum(c.end - c.start for c in calls) / len(calls)

    def non_gemm_ms(self, kind: str) -> float | None:
        calls = [c for c in self._of(kind) if c.gemms]
        if not calls:
            return None
        return sum(1e-6 * (c.end - c.start) - 1e3 * c.gemm_s for c in calls) / len(calls)

    def gemm_roofline(self, kind: str, peaks: dict) -> float | None:
        calls = [c for c in self._of(kind) if c.gemms]
        t = sum(c.gemm_s for c in calls)
        if not t:
            return None
        return 100.0 * sum(c.gemm_roofline_s for c in calls) / t

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def parse(xspace: bytes):
    """A serialized XSpace, as the profiler session returns it."""
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(xspace)


def reduce(pd, anchor: float, win, widths: work.Widths, stats, peaks,
           t_end: float | None = None, device: str = "/device:TPU:0",
           anchor_name: str = "bench.anchor") -> Reduced:
    """Reduce trace ``pd`` (``ProfileData``, or anything with its planes,
    lines and events) to the window ``win``, or to its part before
    ``t_end`` where the trace stopped sooner.  Times are ``perf_counter``
    readings; ``anchor`` is the one taken inside the host annotation
    ``bench.anchor``.  A program call counts where it lies wholly inside."""
    anchor_ns = None
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == anchor_name:
                        anchor_ns = ev.start_ns
    if anchor_ns is None:
        raise ValueError("the trace has no bench.anchor annotation")
    t0 = anchor_ns + (win.t0 - anchor) * 1e9
    t1 = anchor_ns + (min(win.t1, t_end or win.t1) - anchor) * 1e9
    dev = next(p for p in pd.planes if p.name == device)
    lines = {ln.name: ln for ln in dev.lines}

    calls: list[Call] = []
    modules: list[tuple[int, str]] = []
    for ev in lines["XLA Modules"].events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        kind = next((k for k, p in PROGRAMS.items() if p.match(ev.name)), "other")
        if e > t0 and s < t1:
            modules.append((s, kind))
        if kind != "other" and t0 <= s and e <= t1:
            calls.append(Call(kind, s, e))
    decode = [c for c in calls if c.kind == "decode"]
    s0, s1 = win.steps
    if len(decode) <= s1 - s0:  # the window's first steps, in order
        for c, b in zip(decode, stats.bucket_per_step[s0:s1]):
            c.rows = b

    classify = Classifier(widths)
    busy: list[tuple[int, int]] = []
    ops: dict[str, float] = defaultdict(float)
    ci = 0
    for line_name in ("XLA Ops", "Async XLA Ops"):
        if line_name not in lines:
            continue
        ci = 0
        for ev in lines[line_name].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= t0 or s >= t1:
                continue
            sync = line_name == "XLA Ops"
            if sync:  # an asynchronous copy in flight is no op running
                busy.append((max(s, t0), min(e, t1)))
            info = classify(ev.name)
            if info.container:
                continue
            while ci < len(calls) and calls[ci].end <= s:
                ci += 1
            call = calls[ci] if ci < len(calls) and calls[ci].start <= s else None
            where = call.kind if call is not None else "other"
            if sync:
                ops[f"{where}: {info.label}"] += (min(e, t1) - max(s, t0)) * 1e-9
            if call is None or (info.gemm is None and info.weight is None):
                continue
            call.spans.append((max(s, call.start), min(e, call.end)))
            if info.gemm is not None:
                name, M, K, N = info.gemm
                if name == "lm_head":
                    N = widths.vocab
                if call.kind == "decode" and call.rows is not None:
                    M = min(M, call.rows)
                call.gemm_roofline_s += work.roofline_s(work.gemm(M, K, N), peaks) \
                    if peaks else 0.0
                call.gemms += 1
    per_call = widths.layers * (len(work.projections(widths)) - 1) + 1
    missing = sum(max(0, per_call - c.gemms) for c in calls)
    merged = _union(busy)
    busy_s = sum(e - s for s, e in merged) * 1e-9
    gaps = []
    edges = [(t0, t0)] + merged + [(t1, t1)]
    for (_, e_prev), (s_next, _) in zip(edges, edges[1:]):
        if s_next - e_prev <= 0:
            continue
        nxt = next((k for s, k in modules if s >= e_prev), None)
        gaps.append((f"before {NAMES[nxt]}" if nxt else "end of window",
                     (s_next - e_prev) * 1e-9))
    return Reduced(window_s=(t1 - t0) * 1e-9, busy_s=busy_s, calls=calls,
                   ops=dict(ops), gaps=gaps, gemm_missing=missing)
