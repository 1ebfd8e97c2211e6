"""Continuous-batching serve scheduler over the paged KV cache.

The previous serving loop was fixed-batch: all requests prefill together,
decode runs ``max(gen)`` steps for everyone, and a request finishing early
keeps burning its row until the slowest one is done.  This module replaces
it with the production shape:

  * a **request queue** with per-step admission — finished requests are
    evicted the step they complete and freed slots are refilled from the
    queue, so the decode batch tracks the live load;
  * a **slot table** of fixed capacity: slot state (block table, position,
    last token) lives in compacted host arrays sliced to the active bucket
    each step, so jit only ever sees one shape per bucket;
  * **bucket-quantized decode**: the live batch is padded up to the
    smallest tuned batch-size bucket (``core.cmu.DECODE_BUCKETS`` capped at
    the slot capacity) and each bucket dispatches its own pre-tuned CMU
    decode sub-plan — the PR-4 skinny-bm geometries — via
    ``LayerPlan.decode_plan``;
  * **prefill/decode disaggregation**: prefill runs one request at a time
    at a pow2-of-block-size padded prompt length (one jit signature per
    length bucket), scattering K/V straight into the paged block pools;
    decode never sees a prompt.  Cross-request prefill batching is left
    out deliberately: rows of a batched GEMM under a *different* bucket
    plan are a different reduction geometry, which would break the
    batch-composition-independence guarantee the tests pin down.

Determinism contract: greedy decode here is bitwise identical to classic
per-request ``prefill``/``decode_step`` serving, independent of arrival
order, co-scheduled batch composition, and bucket padding — pad slots
write only the reserved scratch block and masked attention scores underflow
to exact zeros, so a request's stream never depends on its neighbours.
The contract holds on the Pallas decode-attention path too
(``cfg.attn_pallas``): the paged flash kernel zeroes masked probabilities
*multiplicatively* (``p = where(live, exp(s - m), 0)``) rather than relying
on additive ``-1e30`` bias underflow alone, so pad rows — whose every key
is masked — contribute exact-zero attention instead of a uniform
distribution over garbage.  ``tests/test_serving.py`` pins stream-vs-
sequential token equality per bucket with the Pallas path enabled.

Fault model (the robustness layer; see docs/serving.md): every request
ends in a terminal ``RequestStatus`` and no failure mode crashes the
trace.  An inadmissible request is **rejected** per-request; queue
overflow (``max_queue``) load-sheds the newest arrival; a request still
queued past its TTL (``deadline``) **times out**; a slot whose decode
logits go non-finite **fails** alone — its stream is truncated at the
poisoned step, its neighbours' streams stay bitwise unchanged.  Pool
starvation (organic or injected) **preempts-and-replays**: the victim's
blocks are freed and the request re-queued carrying its generated-so-far
tokens; on re-admission ``prompt + generated`` replays through prefill,
and because greedy decode is a pure function of the prefix the resumed
stream is bitwise identical to the uninterrupted run
(``RequestStatus.PREEMPTED_RESUMED``).  ``runtime.fault_injection`` makes
every one of those paths deterministically schedulable;
``tests/test_fault_serving.py`` sweeps randomized fault schedules and
pins the replay-determinism property.

Host/device sync discipline: tokens live in a device-resident slot array
and are folded back with lazy ``.at[].set``; the loop never calls
``np.asarray`` per step (the old loop's per-step host sync).  The only
blocking syncs are at admission/eviction/preemption events — where the
host must inspect schedule state anyway — and each one timestamps
``ServeStats.events``.  The non-finite-logit guard rides the same
discipline: decode emits a per-row finiteness flag that accumulates
device-side next to the tokens and is inspected only at the end-of-run
drain (injected poison is additionally evicted eagerly, since the host
scheduled it and needs no readback to know).

Host spans: ``ServeStats.spans`` records what the host did, as
``(name, t0, t1, step, rid)`` on ``time.perf_counter``, each span also
opened as a ``jax.profiler.TraceAnnotation`` so that a profiler trace
shows it on the device's clock.  ``serve.run`` is the root; inside it
``serve.admit`` (one prefill: padded prompt, table, uploads, dispatch),
``serve.decode`` (one step: poison mask, table and position uploads,
dispatch), ``serve.sync`` (``block_until_ready`` at an event) and
``serve.drain`` (the one transfer after the loop).  Spans nest on one
thread; ``serve.run``'s time outside its children is the scheduler's own
bookkeeping.  ``step`` is the decode steps done when the span opened and
``rid`` the request, -1 where none applies.
"""

from __future__ import annotations

import contextlib
import enum
import logging
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cmu import DECODE_BUCKETS
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.runtime.fault_injection import FaultPlan
from repro.runtime.kv_cache import PagedKVCache

log = logging.getLogger(__name__)

# Consecutive empty-slot-table admission retries under injected allocation
# faults before the scheduler sheds the head request instead of spinning.
STARVATION_RETRY_LIMIT = 1024


class RequestStatus(enum.Enum):
    """Terminal state of a served request.  Every request a trace hands to
    ``ServeScheduler.run`` ends in exactly one of these — the scheduler
    never raises for a per-request condition."""

    OK = "ok"                              # completed, never disturbed
    REJECTED = "rejected"                  # inadmissible or load-shed
    TIMEOUT = "timeout"                    # queue-wait TTL exceeded
    PREEMPTED_RESUMED = "preempted_resumed"  # completed after >=1 replay
    FAILED = "failed"                      # non-finite logits / no progress

    @property
    def completed(self) -> bool:
        """True when the request finished with its full token stream."""
        return self in (RequestStatus.OK, RequestStatus.PREEMPTED_RESUMED)


@dataclass
class Request:
    """One serving request: ``max_new`` greedy tokens from ``prompt``.

    ``arrival`` is a virtual timestamp in decode-step units — the scheduler
    admits a request only once its arrival step has passed, which is how
    the benchmark replays a Poisson trace without wall-clock sleeps.
    ``deadline`` (steps, from arrival) bounds the queue wait for this
    request alone; None defers to the scheduler-wide TTL."""

    rid: int
    prompt: np.ndarray
    max_new: int
    arrival: int = 0
    deadline: int | None = None


@dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray | None  # filled by the end-of-run drain
    admitted_step: int         # first admission (-1 if never admitted)
    finished_step: int         # terminal step (-1 if rejected up front)
    status: RequestStatus = RequestStatus.OK
    preemptions: int = 0


@dataclass
class ServeStats:
    capacity: int
    steps: int = 0
    prefills: int = 0
    tokens: int = 0
    preemptions: int = 0
    replays: int = 0
    rejections: int = 0
    timeouts: int = 0
    failures: int = 0
    faults_injected: dict[str, int] = field(default_factory=dict)
    # the KV pools as built: K and V's device bytes (padding included) and
    # the positions they hold (num_blocks × block_size)
    kv_pool_bytes: int = 0
    kv_pool_positions: int = 0
    active_per_step: list[int] = field(default_factory=list)
    bucket_per_step: list[int] = field(default_factory=list)
    # (decode steps so far, tokens so far, perf_counter) at every sync event
    events: list[tuple[int, int, float]] = field(default_factory=list)
    # (name, perf_counter at start, at end, step, rid) per host span
    spans: list[tuple[str, float, float, int, int]] = field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str, *, step: int = -1, rid: int = -1):
        """Record the host time of the block as a span, inside a profiler
        annotation of the same name (inactive unless a trace runs)."""
        with jax.profiler.TraceAnnotation(name, step=step, rid=rid):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter(), step, rid))

    def host_seconds(self) -> dict[str, float]:
        """Seconds per span name, and ``bookkeeping``: ``serve.run``'s time
        outside its child spans."""
        out: dict[str, float] = {}
        for name, t0, t1, _, _ in self.spans:
            out[name] = out.get(name, 0.0) + (t1 - t0)
        root = out.pop("serve.run", 0.0)
        out["bookkeeping"] = root - sum(out.values())
        return out

    @property
    def slot_utilization(self) -> float:
        if not self.steps:
            return 0.0
        return sum(self.active_per_step) / (self.steps * self.capacity)

    def bucket_histogram(self) -> dict[int, int]:
        h: dict[int, int] = {}
        for b in self.bucket_per_step:
            h[b] = h.get(b, 0) + 1
        return dict(sorted(h.items()))


@dataclass
class _Slot:
    rid: int
    pos: int        # next cache write position = tokens already cached
    remaining: int  # decode steps left (this incarnation)
    blocks: list[int]
    admitted_step: int


def _pow2_at_least(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _jit_steps(model):
    """Jitted (greedy prefill, greedy decode) paged steps, cached on the
    model: every ``ServeScheduler`` for the same model shares one jit cache,
    so a fresh scheduler (the benchmark builds several) never recompiles
    already-traced (prompt-bucket, batch-bucket) signatures.

    Both steps emit a per-row **finiteness flag** next to the sampled token
    (the non-finite-logit guard's observable), and decode takes a per-row
    ``poison`` mask — the fault-injection seam that overwrites a row's
    logits with NaN *inside* the step.  With the mask all-False the logits
    pass through ``where`` untouched, so the determinism contract is
    bitwise intact on the clean path."""
    cached = getattr(model, "_paged_jit_steps", None)
    if cached is not None:
        return cached
    pf = make_prefill_step(model, paged=True)
    dc = make_decode_step(model, paged=True)

    def prefill_fn(params, tokens, lens, table, pool_k, pool_v):
        last, pk, pv = pf(params, {"tokens": tokens}, lens, table, pool_k, pool_v)
        ok = jnp.isfinite(last.astype(jnp.float32)).all(-1)
        return jnp.argmax(last, -1).astype(jnp.int32), ok, pk, pv

    def decode_fn(params, pool_k, pool_v, table, positions, token, poison):
        logits, pk, pv = dc(params, pool_k, pool_v, table, positions, token)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        ok = jnp.isfinite(logits.astype(jnp.float32)).all(-1)
        return jnp.argmax(logits, -1).astype(jnp.int32), ok, pk, pv

    steps = (jax.jit(prefill_fn, donate_argnums=(4, 5)),
             jax.jit(decode_fn, donate_argnums=(1, 2)))
    model._paged_jit_steps = steps
    return steps


def serve_buckets(capacity: int) -> tuple[int, ...]:
    """The decode batch buckets for a slot capacity: every tuned bucket
    below it, plus the capacity itself."""
    return tuple(sorted({b for b in DECODE_BUCKETS if b < capacity} | {capacity}))


def poisson_trace(n: int, *, vocab: int, max_prompt: int, max_gen: int,
                  rate: float = 0.0, seed: int = 0, min_prompt: int = 4,
                  min_gen: int = 2) -> list[Request]:
    """Synthetic request trace: Poisson arrivals (exponential interarrivals
    in decode-step units; ``rate <= 0`` lands everything at step 0) with
    uniformly mixed prompt/generation lengths."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        if rate > 0:
            t += rng.exponential(1.0 / rate)
        p = int(rng.integers(min_prompt, max_prompt + 1))
        g = int(rng.integers(min_gen, max_gen + 1))
        prompt = rng.integers(0, vocab, size=p).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=g, arrival=int(t)))
    return reqs


class ServeScheduler:
    """Continuous-batching greedy decoder over a paged KV cache.

    ``capacity`` slots; each admitted request gets its blocks for
    ``prompt + max_new - 1`` cache positions up front (no mid-flight OOM),
    a queue position otherwise.  ``run(requests)`` replays a trace and
    returns ``({rid: RequestResult}, ServeStats)`` with every request in a
    terminal ``RequestStatus`` — per-request failures degrade, they never
    crash the trace.

    Robustness knobs: ``deadline`` is the queue-wait TTL in decode steps
    (a request still waiting ``deadline`` steps after arrival times out;
    preempted requests re-enter the queue with a fresh arrival),
    ``max_queue`` bounds the waiting queue (the newest arrival is load-shed
    when it would overflow), and ``faults`` threads a deterministic
    ``runtime.fault_injection.FaultPlan`` through the scheduler's fault
    seams (allocation, decode logits, preemption, latency).
    """

    def __init__(self, model, params, *, capacity: int = 8,
                 block_size: int = 16, max_total_len: int,
                 num_blocks: int | None = None,
                 deadline: int | None = None,
                 max_queue: int | None = None,
                 faults: FaultPlan | None = None):
        cfg = model.cfg
        if cfg.family not in ("dense", "moe", "vlm"):
            raise NotImplementedError(
                f"continuous batching covers dense/moe/vlm, not {cfg.family}")
        self.model = model
        self.params = params
        self.capacity = capacity
        self.block_size = block_size
        self.deadline = deadline
        self.max_queue = max_queue
        self.faults = faults
        self.buckets = serve_buckets(capacity)
        # table width: blocks for the longest admissible request
        self.max_blocks = -(-max_total_len // block_size)
        if num_blocks is None:
            num_blocks = capacity * self.max_blocks + 1  # +1 scratch
        self.kv = PagedKVCache(cfg, num_blocks, block_size)
        self.kv_pool_bytes = self.kv.device_bytes
        if faults is not None:
            self.kv.allocator.fault_hook = faults.fail_alloc

        self._prefill, self._decode = _jit_steps(model)

    # -- sizing ------------------------------------------------------------

    def total_len(self, r: Request) -> int:
        """Cache positions a request needs: prompt + all but the last
        generated token (the last one is sampled but never cached)."""
        return len(r.prompt) + r.max_new - 1

    def prompt_bucket(self, p: int) -> int:
        return _pow2_at_least(max(p, self.block_size), self.block_size)

    def bucket(self, active: int) -> int:
        for b in self.buckets:
            if active <= b:
                return b
        raise AssertionError(f"{active} active > capacity {self.capacity}")

    def admissible(self, r: Request) -> bool:
        """Whether the pool could ever hold this request: its block need
        fits the table width and the (empty) pool."""
        return self.kv.blocks_for(self.total_len(r)) <= min(
            self.max_blocks, self.kv.num_blocks - 1)

    # -- the loop ----------------------------------------------------------

    def run(self, requests: list[Request]) -> tuple[dict[int, RequestResult], ServeStats]:
        stats = ServeStats(capacity=self.capacity,
                           kv_pool_bytes=self.kv_pool_bytes,
                           kv_pool_positions=self.kv.positions)
        with stats.span("serve.run"):
            results = self._run(requests, stats)
        return results, stats

    def _run(self, requests: list[Request], stats: ServeStats) -> dict[int, RequestResult]:
        results: dict[int, RequestResult] = {}
        faults = self.faults
        if faults is not None:
            faults.reset()

        # per-request admissibility: reject the oversized request, keep the
        # trace alive (the pre-robustness scheduler raised for everyone)
        admissible: list[Request] = []
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            if self.admissible(r):
                admissible.append(r)
                continue
            log.warning(
                "request %d needs %d cache positions; pool is %d blocks x %d"
                " — rejected", r.rid, self.total_len(r), self.max_blocks,
                self.block_size)
            results[r.rid] = RequestResult(
                rid=r.rid, tokens=None, admitted_step=-1, finished_step=-1,
                status=RequestStatus.REJECTED)
            stats.rejections += 1

        pending = deque(admissible)
        waiting: deque[Request] = deque()
        slots: list[_Slot] = []
        origin = {r.rid: r for r in requests}   # pre-preemption identity
        first_admit: dict[int, int] = {}
        preempts: dict[int, int] = {}
        C, nb = self.capacity, self.max_blocks
        tables = np.zeros((C, nb), np.int32)      # pad rows -> scratch block
        positions = np.zeros((C,), np.int32)
        tok = jnp.zeros((C,), jnp.int32)          # device-resident slot tokens
        pool_k, pool_v = self.kv.k, self.kv.v
        step = 0
        starved = 0
        tokens_out = 0
        # per decode step: (token array (bucket,), finite flags, rids of
        # active slots); prefill first-tokens ride the same list —
        # everything is fetched from device in ONE transfer after the loop
        # (`drain`), never per step
        emitted: list[tuple[jax.Array, jax.Array, tuple[int, ...]]] = []

        def note_event():
            with stats.span("serve.sync", step=stats.steps):
                jax.block_until_ready(tok)
            stats.events.append((stats.steps, tokens_out, time.perf_counter()))

        def remove_slot(i: int, status: RequestStatus | None):
            """Free slot ``i`` with swap-with-last compaction.  ``status``
            None means preemption: blocks return but no result is final."""
            nonlocal tok
            s = slots[i]
            if status is not None:
                results[s.rid] = RequestResult(
                    rid=s.rid, tokens=None,
                    admitted_step=first_admit.get(s.rid, s.admitted_step),
                    finished_step=step, status=status,
                    preemptions=preempts.get(s.rid, 0))
            self.kv.free(s.blocks)
            last = len(slots) - 1
            if i != last:
                slots[i] = slots[last]
                tables[i] = tables[last]
                positions[i] = positions[last]
                tok = tok.at[i].set(tok[last])
            slots.pop()
            tables[len(slots)] = 0
            positions[len(slots)] = 0
            return s

        def evict_finished():
            done = [i for i, s in enumerate(slots) if s.remaining == 0]
            for i in reversed(done):  # compact from the back: swap-with-last
                rid = slots[i].rid
                remove_slot(i, RequestStatus.PREEMPTED_RESUMED
                            if preempts.get(rid) else RequestStatus.OK)
            return bool(done)

        def preempt(i: int):
            """Free the victim's blocks and re-queue it carrying its
            generated-so-far tokens; re-admission replays the prefix."""
            s = remove_slot(i, None)
            gen = self._generated(emitted, s.rid)
            r0 = origin[s.rid]
            resumed = Request(
                rid=s.rid, prompt=np.concatenate([r0.prompt, gen]),
                max_new=r0.max_new - len(gen), arrival=step,
                deadline=r0.deadline)
            waiting.appendleft(resumed)  # it held a slot: front of the line
            preempts[s.rid] = preempts.get(s.rid, 0) + 1
            stats.preemptions += 1

        def shed_expired():
            if self.deadline is None and all(
                    r.deadline is None for r in waiting):
                return
            kept: deque[Request] = deque()
            while waiting:
                r = waiting.popleft()
                ttl = r.deadline if r.deadline is not None else self.deadline
                if ttl is not None and step - r.arrival > ttl:
                    results[r.rid] = RequestResult(
                        rid=r.rid, tokens=None,
                        admitted_step=first_admit.get(r.rid, -1),
                        finished_step=step, status=RequestStatus.TIMEOUT,
                        preemptions=preempts.get(r.rid, 0))
                    stats.timeouts += 1
                else:
                    kept.append(r)
            waiting.extend(kept)

        note_event()
        while pending or waiting or slots:
            while pending and pending[0].arrival <= step:
                waiting.append(pending.popleft())
            shed_expired()
            synced = False
            while waiting and len(slots) < C:
                r = waiting[0]
                blocks = self.kv.alloc(self.total_len(r))
                if blocks is None:
                    break  # pool exhausted: FIFO-wait for evictions
                waiting.popleft()
                starved = 0
                first_admit.setdefault(r.rid, step)
                if preempts.get(r.rid):
                    stats.replays += 1
                with stats.span("serve.admit", step=stats.steps, rid=r.rid):
                    tok, pool_k, pool_v, first, ok = self._admit(
                        r, len(slots), blocks, slots, tables, positions, tok,
                        pool_k, pool_v, step)
                emitted.append((first, ok, (r.rid,)))
                tokens_out += 1
                stats.prefills += 1
                synced |= evict_finished()  # max_new == 1: done at prefill
                synced = True
            # bounded admission: the queue never grows past max_queue —
            # the newest arrival is load-shed (the head keeps its FIFO turn)
            while self.max_queue is not None and len(waiting) > self.max_queue:
                r = waiting.pop()
                results[r.rid] = RequestResult(
                    rid=r.rid, tokens=None,
                    admitted_step=first_admit.get(r.rid, -1),
                    finished_step=step, status=RequestStatus.REJECTED,
                    preemptions=preempts.get(r.rid, 0))
                stats.rejections += 1
            if synced:
                note_event()
            if not slots:
                if pending and not waiting:
                    step = max(step, pending[0].arrival)  # idle: skip ahead
                    continue
                if waiting:
                    # empty slot table + a queued admissible request: only
                    # injected allocation faults (transient) or a leak can
                    # cause this.  Retry; past the retry budget, shed the
                    # head — degrade, never crash.
                    starved += 1
                    if (faults is not None and starved <= STARVATION_RETRY_LIMIT):
                        step += 1
                        continue
                    r = waiting.popleft()
                    log.error(
                        "pool cannot satisfy admissible request %d with an "
                        "empty slot table — shedding it as FAILED", r.rid)
                    results[r.rid] = RequestResult(
                        rid=r.rid, tokens=None,
                        admitted_step=first_admit.get(r.rid, -1),
                        finished_step=step, status=RequestStatus.FAILED,
                        preemptions=preempts.get(r.rid, 0))
                    continue
                break
            with stats.span("serve.decode", step=stats.steps):
                b = self.bucket(len(slots))
                poison = np.zeros((b,), bool)
                poisoned = None
                if faults is not None:
                    dt = faults.spike()
                    if dt:
                        time.sleep(dt)
                    poisoned = faults.pick_poison(step, len(slots))
                    if poisoned is not None:
                        poison[poisoned] = True
                # host copies: the step runs asynchronously, and on the CPU
                # backend jnp.asarray aliases the numpy buffer, which the
                # bookkeeping below mutates before the step may have read it
                tok_b, ok_b, pool_k, pool_v = self._decode(
                    self.params, pool_k, pool_v,
                    jnp.asarray(tables[:b].copy()), jnp.asarray(positions[:b].copy()),
                    tok[:b], jnp.asarray(poison))
                tok = tok.at[:b].set(tok_b)
            step += 1
            stats.steps += 1
            stats.active_per_step.append(len(slots))
            stats.bucket_per_step.append(b)
            emitted.append((tok_b, ok_b, tuple(s.rid for s in slots)))
            tokens_out += len(slots)
            for s in slots:
                s.pos += 1
                s.remaining -= 1
            positions[:len(slots)] += 1
            if poisoned is not None:
                # the host scheduled this poison: evict the failed slot
                # eagerly (no readback needed); the drain truncates its
                # stream at the poisoned token via the finiteness flags
                remove_slot(poisoned, RequestStatus.FAILED)
                synced = True
            else:
                synced = False
            synced |= evict_finished()
            if faults is not None and slots:
                victim = faults.pick_preempt(step, len(slots))
                if victim is not None:
                    note_event()  # the replay prefix needs a token readback
                    preempt(victim)
                    synced = True
            if synced:
                note_event()
        note_event()
        self.kv.k, self.kv.v = pool_k, pool_v
        stats.tokens = tokens_out
        with stats.span("serve.drain"):
            self._drain(emitted, results)
        stats.failures = sum(
            1 for res in results.values()
            if res.status is RequestStatus.FAILED)
        if faults is not None:
            stats.faults_injected = dict(faults.injected)
        missing = {r.rid for r in requests} - set(results)
        assert not missing, f"requests {missing} ended without a status"
        return results

    def _admit(self, r: Request, row: int, blocks: list[int], slots, tables,
               positions, tok, pool_k, pool_v, step: int):
        """Prefill one request into ``row``: pad the prompt to its length
        bucket, scatter K/V through a prefill block table (entries past the
        allocation -> scratch), and seed the slot with the first sampled
        token."""
        p = len(r.prompt)
        sb = self.prompt_bucket(p)
        prompt = np.zeros((1, sb), np.int32)
        prompt[0, :p] = r.prompt
        nb_p = sb // self.block_size
        ptable = np.zeros((1, nb_p), np.int32)
        for j in range(min(nb_p, len(blocks))):
            ptable[0, j] = blocks[j]
        first, ok, pool_k, pool_v = self._prefill(
            self.params, jnp.asarray(prompt),
            jnp.asarray(np.array([p], np.int32)), jnp.asarray(ptable),
            pool_k, pool_v)
        tables[row] = 0
        tables[row, :len(blocks)] = blocks
        positions[row] = p
        tok = tok.at[row].set(first[0])
        slots.append(_Slot(rid=r.rid, pos=p, remaining=r.max_new - 1,
                           blocks=blocks, admitted_step=step))
        return tok, pool_k, pool_v, first, ok

    def _generated(self, emitted, rid: int) -> np.ndarray:
        """This request's generated-so-far tokens (all incarnations), read
        back from the emitted stream — the replay prefix for preemption."""
        picks = [(j, rids.index(rid)) for j, (_, _, rids) in enumerate(emitted)
                 if rid in rids]
        host = jax.device_get([emitted[j][0] for j, _ in picks])
        return np.asarray([int(a[col]) for a, (_, col) in zip(host, picks)],
                          np.int32)

    def _drain(self, emitted, results) -> None:
        """One device->host transfer for every token of the run, then
        scatter them back into per-request streams.  The non-finite-logit
        guard lands here: a stream whose finiteness flag dropped is
        truncated at the first poisoned token and its request marked
        FAILED — neighbours' streams are untouched."""
        host_tok = jax.device_get([t for t, _, _ in emitted])
        host_ok = jax.device_get([o for _, o, _ in emitted])
        streams: dict[int, list[int]] = {}
        fine: dict[int, list[bool]] = {}
        for arr, oks, (_, _, rids) in zip(host_tok, host_ok, emitted):
            for i, rid in enumerate(rids):
                streams.setdefault(rid, []).append(int(arr[i]))
                fine.setdefault(rid, []).append(bool(oks[i]))
        for rid, toks in streams.items():
            flags = fine[rid]
            if all(flags):
                results[rid].tokens = np.asarray(toks, np.int32)
            else:
                bad = flags.index(False)
                results[rid].tokens = np.asarray(toks[:bad], np.int32)
                results[rid].status = RequestStatus.FAILED


def run_fixed_batch(model, params, requests: list[Request], *,
                    cache_len: int | None = None):
    """The pre-scheduler fixed-batch serving loop, kept as the benchmark
    baseline: every prompt right-padded to the longest, one joint prefill,
    then ``max(max_new)`` decode steps for the whole batch — early
    finishers burn their row until the last request completes.  Tokens stay
    on device until one final transfer (the old loop's per-step
    ``np.asarray`` host sync is gone here too).

    Note the classic semantics: with mixed prompt lengths the joint prefill
    samples every row at the padded last column, so this is a throughput
    baseline, not a correctness reference — the sequential reference for
    that is per-request classic decode (see ``launch.serve``).
    """
    B = len(requests)
    pmax = max(len(r.prompt) for r in requests)
    gmax = max(r.max_new for r in requests)
    if cache_len is None:
        cache_len = _pow2_at_least(pmax + gmax, 16)
    prompt = np.zeros((B, pmax), np.int32)
    for i, r in enumerate(requests):
        prompt[i, :len(r.prompt)] = r.prompt
    # same per-model jit caching as the scheduler path, so repeat baseline
    # runs (warm-up + measured) don't recompile and the comparison is honest
    cached = getattr(model, "_classic_jit_steps", None)
    if cached is None or cached[0] != cache_len:
        prefill = jax.jit(make_prefill_step(model, cache_len))
        decode = jax.jit(make_decode_step(model), donate_argnums=(1,))
        model._classic_jit_steps = cached = (cache_len, prefill, decode)
    _, prefill, decode = cached

    t0 = time.perf_counter()
    cache, last = prefill(params, {"tokens": jnp.asarray(prompt)})
    tok = jnp.argmax(last, -1).astype(jnp.int32)
    outs = [tok]
    for _ in range(gmax - 1):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(tok)
    jax.block_until_ready(tok)
    wall = time.perf_counter() - t0

    host = np.stack(jax.device_get(outs), axis=1)  # (B, gmax)
    results = {r.rid: host[i, :r.max_new] for i, r in enumerate(requests)}
    useful = sum(r.max_new for r in requests)
    return results, {"walltime_s": wall, "useful_tokens": useful,
                     "row_steps": B * gmax, "decode_steps": gmax - 1}
