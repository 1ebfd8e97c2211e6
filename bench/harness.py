"""One run of one cell: set-up, the measured window, the metrics, the check.

Everything that belongs to one cell, configuration, traffic mix or metric
is data found by name:

  BENCHMARK.json                 the cells, metrics and bounds
  bench/configs/<config>.json    the published configuration, the program's
                                 arch and flags, the plain reference's name
  bench/traffic/<mix>.json       lengths, arrivals, block size (traffic.py)
  bench/cells/<cell>.json        slots and KV-pool positions (the chip's
                                 share of memory), the size of the
                                 correctness sample, the limits
  bench/plans/<cell>.json        the cell's CMU plan, tuned once on the chip
  bench/metrics/<metric>.py      ``read(ctx) -> float | None``, one per metric
  bench/references/<name>.py     the plain reference ``make_forward``
  bench/peaks.json               the chip's peaks by ``device_kind``

A run drives the program only through its serving entry points
(``repro.launch.serve.parse_args / serve_config / setup_plan``,
``ServeScheduler.run``, ``ServeStats``, ``RequestResult``):

 1. load the cell's CMU plan: the committed ``bench/plans/<cell>.json``,
    copied to ``.bench_state/plans/<cell>.json``, so that every checkout
    serves under the same plan; a cell with no committed plan tunes one on
    its first run in a checkout;
 2. make the weights on the device from the seed (``weights.py``);
 3. warm up the cell's prompt buckets and decode buckets, then time the
    same warm-up again to size the trace;
 4. generate the trace from the seed: every request arrives at once, and
    the queue holds ``QUEUE_MARGIN`` times what the warm-up says the window
    will serve;
 5. serve it.  The window (``window.py``) runs from the first sync event
    with every slot full to the first one ``--seconds`` later, or to the
    one at which the queue first empties.

Set-up is everything from the start of the process to the start of the
window.  The drain after the window is neither.  With ``--trace 1`` the
serve runs under the profiler and the per-layer metrics are read from the
trace and the scheduler's counters; without, the end-to-end metrics.
Then the program's state is freed and ``correct.py`` compares a sample of
the served tokens with the plain reference.
"""

from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import correct, traffic, window, work

STATE = ".bench_state"  # plans and traces, inside the checkout
# the device trace covers the serve's first seconds: the slots filling, then
# the head of the window (the per-layer numbers of the device trace read it)
TRACE_SECONDS = 6.0
# the queue holds this many times the requests the window is expected to
# serve, so that it outlasts the window when the estimate is a little off
QUEUE_MARGIN = 1.3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    mix: traffic.Mix
    params: dict            # bench/cells/<cell>.json
    end_to_end: list[dict]  # the manifest's metrics this cell reports
    per_layer: list[dict]


def pool_blocks(params: dict, block_size: int) -> int | None:
    """The KV pool's blocks for a cell that states ``kv_positions`` (one
    more: the scheduler's scratch block), else None: a pool that reserves
    the longest request's positions for every slot.  Each request holds
    the blocks of its own length, so a pool smaller than that lets more
    slots share the memory, and a request waits while the pool is full."""
    if "kv_positions" not in params:
        return None
    return -(-int(params["kv_positions"]) // block_size) + 1


def load_cell(root: Path, name: str) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    config = json.loads((root / files[w["config"]]).read_text())
    mix = traffic.Mix.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    params = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, int(w["chips"]), config, mix, params, e2e, per_layer)


def _load(path: Path):
    """A module from a file of the benchmark (its name may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    return _load(root / "bench" / "metrics" / f"{metric}.py").read


def load_reference(root: Path, name: str):
    """``make_forward(config, control=False)`` of ``bench/references/<name>.py``."""
    return _load(root / "bench" / "references" / f"{name}.py").make_forward


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


class Compiles:
    """Compile seconds, persistent-cache hits and misses, and the host time
    of every compile, from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = self.misses = 0
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs
            self.times.append(time.perf_counter())

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def published_widths_match(cfg, c: dict) -> None:
    """The program's config must be the published one (the harness passes
    the overrides the config file states); anything else is an error."""
    L = int(c["num_hidden_layers"])
    H = int(c["num_attention_heads"])
    want = {
        "num_layers": L, "d_model": int(c["hidden_size"]), "num_heads": H,
        "num_kv_heads": int(c.get("num_key_value_heads", H)),
        "head_dim": int(c.get("head_dim") or int(c["hidden_size"]) // H),
        "d_ff": int(c["intermediate_size"]), "vocab_size": int(c["vocab_size"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c.get("rope_theta", 10000.0)),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "qk_norm": c["model_type"] == "qwen3",
        "emb_scale": float(c.get("scale_emb", 1.0)),
        "residual_scale": (float(c["scale_depth"]) / math.sqrt(L)
                           if "scale_depth" in c else 1.0),
        "activation": c.get("hidden_act", "silu"),
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if (getattr(cfg, k) != v if isinstance(v, str) else
               not math.isclose(float(getattr(cfg, k)), float(v), rel_tol=1e-9))}
    if bad:
        raise SystemExit(f"program config differs from the published one "
                         f"(program, published): {bad}")


@dataclass
class Ctx:
    """What a metric reader may read."""

    cell: Cell
    widths: work.Widths
    peaks: dict
    capacity: int
    stats: object                    # the scheduler's ServeStats
    win: window.Window
    setup_s: float
    prompt_bucket: object            # ServeScheduler.prompt_bucket
    requests: dict                   # rid -> the served Request
    results: dict                    # rid -> its RequestResult
    trace: object = None             # trace_reduce.Reduced with --trace 1

    def required_roofline_s(self) -> float:
        """Roofline seconds of the work the model requires in the window:
        each prefill admitted in it and each decode step of it."""
        pf = sum(work.roofline_s(work.prefill(self.widths, len(self.requests[rid].prompt)),
                                 self.peaks) for rid in self.win.prefilled())
        steps = window.cached_per_step(self.win, self.stats, self.results,
                                       list(self.requests.values()))
        return pf + sum(work.roofline_s(work.decode_step(self.widths, c), self.peaks)
                        for c in steps)


def warm_trace(mix: traffic.Mix, capacity: int, bucket_of, vocab: int, Request):
    """``capacity`` requests at once, or one per prompt bucket where the
    mix has more buckets than slots: prompts at each of the mix's prompt
    buckets in turn, at the shortest length the bucket holds (so that they
    all fit the pool at once), and 2, 3, ... new tokens, so that one
    request leaves per step and the decode batch passes through every
    bucket from ``capacity`` down."""
    buckets = sorted({bucket_of(p) for p in mix.prompt_lengths()})
    rng = np.random.default_rng(0)
    out = []
    for i in range(max(capacity, len(buckets))):
        b = buckets[i % len(buckets)]
        n = min(p for p in range(b // 2 + 1, b + 1) if bucket_of(p) == b)
        out.append(Request(rid=i, prompt=rng.integers(0, vocab, size=n, dtype=np.int32),
                           max_new=2 + i))
    return out


def serve_window_estimate(stats, warm, capacity: int, mix: traffic.Mix, bucket_of):
    """Seconds per request of the mix, from a compiled warm-up pass: prefill
    seconds per bucket token up to the first sync after the admissions,
    decode seconds per step after it."""
    ev = stats.events
    admitted = [e for e in ev if e[0] == 0 and e[1] > 0]
    t_pref = admitted[0][2] - ev[0][2]
    per_token = t_pref / sum(bucket_of(len(r.prompt)) for r in warm)
    per_step = (ev[-1][2] - admitted[0][2]) / max(stats.steps, 1)
    ps, gs = mix.prompt_lengths(), mix.output_lengths()
    mean_bucket = sum(bucket_of(p) for p in ps) / len(ps)
    mean_new = sum(gs) / len(gs)
    return mean_bucket * per_token + (mean_new - 1) * per_step / capacity


class Tracer:
    """The profiler over the first ``seconds`` of the serve: started here,
    stopped by a timer thread while the scheduler runs (or at ``close``,
    whichever comes first), so that a trace stays well under a million
    events whatever the window's length.  The trace is kept in memory
    (``xspace``) and never written out: exporting it to files takes
    minutes at that size."""

    def __init__(self, seconds: float):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # start_trace wants a directory; nothing is written to it
        jax.profiler.start_trace(STATE, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            self.anchor = time.perf_counter()
        self.t_stop: float | None = None
        self.xspace: bytes | None = None
        self._lock = threading.Lock()
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.start()

    def stop(self) -> None:
        from jax._src import profiler

        with self._lock:
            if self.t_stop is None:
                self.t_stop = time.perf_counter()
                with profiler._profile_state.lock:
                    self.xspace = profiler._profile_state.profile_session.stop()
                    profiler._profile_state.reset()

    def close(self) -> None:
        self._timer.cancel()
        self.stop()
        self._timer.join()


class Program:
    """The system under test, set up for ``cell``: its config (checked
    against the published one), its CMU plan (loaded, or tuned on the
    cell's first run in this checkout) and its model."""

    def __init__(self, root: Path, cell: Cell):
        sys.path.insert(0, str(root / "src"))
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.scheduler import Request, ServeScheduler
        from repro.models import Model

        enable_compile_cache()
        self.Request, self.ServeScheduler = Request, ServeScheduler
        c, prog, mix = cell.config, cell.config["program"], cell.mix
        self.cell, self.capacity = cell, int(cell.params["slots"])
        plan_path = root / STATE / "plans" / f"{cell.name}.json"
        plan_path.parent.mkdir(parents=True, exist_ok=True)
        committed = root / "bench" / "plans" / f"{cell.name}.json"
        if committed.exists() and not plan_path.exists():
            shutil.copyfile(committed, plan_path)
        # the CMU tunes the prefill projections at the median prompt's bucket
        median = mix.prompt_lengths()[len(mix.prompt_lengths()) // 2]
        plan_tokens = max(mix.block_size, 1 << (median - 1).bit_length())
        args = serve.parse_args(
            ["--arch", prog["arch"], *prog["flags"], "--slots", str(self.capacity),
             "--block-size", str(mix.block_size), "--requests", "1",
             "--prompt-len", str(plan_tokens), "--plan-cache", str(plan_path)])
        self.cfg = serve.serve_config(args).replace(**prog.get("overrides", {}))
        published_widths_match(self.cfg, c)
        tuned = not plan_path.exists()
        serve.setup_plan(args, self.cfg, None)
        if tuned:
            # serve under the plan as its file holds it, as every later run
            # does: served under the tuner's own plan, a checkout's first run
            # left its second one compile short in the warm-up
            serve.setup_plan(args, self.cfg, None)
        self.model = Model(self.cfg)

    def weights(self, seed: int):
        """The benchmark's weights for ``seed``, in the program's layout."""
        import jax

        from bench import weights as W

        params = jax.block_until_ready(W.make(
            self.cell.config, seed, qk_norm=self.cfg.qk_norm,
            tied=self.cfg.tie_embeddings))
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        shape_of = lambda a: (tuple(a.shape), str(a.dtype))  # noqa: E731
        if jax.tree.map(shape_of, params) != jax.tree.map(shape_of, want):
            raise SystemExit("the program's parameter layout differs from "
                             "bench/weights.py's")
        return params

    def scheduler(self, params):
        mix = self.cell.mix
        return self.ServeScheduler(self.model, params, capacity=self.capacity,
                                   block_size=mix.block_size,
                                   max_total_len=mix.max_total_len,
                                   num_blocks=pool_blocks(self.cell.params,
                                                          mix.block_size))


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        root: Path, t_start: float, require_tpu: bool = True,
        program=None) -> dict:
    """One run of ``cell_name``; returns the result object.

    ``require_tpu=False`` (tests only) skips the look for a chip.
    ``program`` (tests only) is a callable applied to the scheduler before
    the trace is served, to break the timed path underneath."""
    cell = load_cell(root, cell_name)
    import jax

    devices = check_device(cell.chips) if require_tpu else jax.devices()[:1]
    dev = devices[0]
    peaks = work.load_peaks(root / "bench" / "peaks.json", dev.device_kind) \
        if require_tpu else None

    compiles = Compiles()
    phases: dict[str, float] = {}

    def phase(name, fn, *a):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            out = fn(*a)
        phases[name] = time.perf_counter() - t0
        return out

    prog = phase("plan", Program, root, cell)
    c, mix, capacity, Request = cell.config, cell.mix, prog.capacity, prog.Request
    params = phase("weights", prog.weights, seed)

    sched = prog.scheduler(params)
    vocab = int(c["vocab_size"])
    warm = warm_trace(mix, capacity, sched.prompt_bucket, vocab, Request)
    phase("warm_up", sched.run, warm)
    _, wstats = phase("calibrate", sched.run, warm)
    per_request = serve_window_estimate(wstats, warm, capacity, mix,
                                        sched.prompt_bucket)
    n = capacity + math.ceil(QUEUE_MARGIN * seconds / per_request)
    reqs = [Request(rid=i, prompt=p, max_new=g)
            for i, (p, g) in enumerate(mix.requests(seed, n, vocab))]
    if program is not None:
        program(sched)

    tracer = Tracer(TRACE_SECONDS) if trace else None
    try:
        results, stats = sched.run(reqs)
    finally:
        if tracer is not None:
            tracer.close()
    t_served = time.perf_counter()

    win = window.select(stats, results, reqs, capacity, seconds)
    setup_s = win.t0 - t_start
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    in_window = compiles.between(win.t0, win.t1)

    widths = work.Widths.from_config(c)
    ctx = Ctx(cell=cell, widths=widths, peaks=peaks, capacity=capacity,
              stats=stats, win=win, setup_s=setup_s,
              prompt_bucket=sched.prompt_bucket,
              requests={r.rid: r for r in reqs}, results=results)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    breakdown, extra_trace = None, {}
    if trace:
        from bench import trace_reduce

        red = trace_reduce.reduce(trace_reduce.parse(tracer.xspace), tracer.anchor,
                                  win, widths, stats, peaks, t_end=tracer.t_stop - 0.05)
        ctx.trace = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        extra_trace = {"trace_calls": {k: len(red._of(k)) for k in ("prefill", "decode")},
                       "gemm_missing": red.gemm_missing}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's state (the scheduler's pools) goes before the reference
    # runs; the context holds the scheduler through ``prompt_bucket``
    del sched, ctx
    served = {r.rid: results[r.rid].tokens for r in reqs
              if results[r.rid].tokens is not None}
    failed = sum(not results[r.rid].status.completed for r in reqs)
    t_check = time.perf_counter()
    make_forward = load_reference(root, c["reference"])
    check = correct.check(make_forward(c), params, reqs, served, seed,
                          int(cell.params["check_tokens"]), vocab)
    checks = {
        "max_logit_gap": {"value": check.max_gap,
                          "limit": cell.params["limits"]["max_logit_gap"]},
        "requests_failed": {"value": failed, "limit": 0},
        "tokens_short": {"value": sum(
            r.max_new - len(served.get(r.rid, ())) for r in reqs), "limit": 0},
    }
    ok = all(v["value"] <= v["limit"] for v in checks.values())
    extra = {
        "setup_phases_s": phases, "compile_s": compiles.seconds,
        "cache_hits": compiles.hits, "cache_misses": compiles.misses,
        "compiles_in_window": in_window, "window_s": win.seconds,
        "window_steps": win.steps, "window_prefills": win.prefills,
        "requests": len(reqs), "drain_s": t_served - win.t1,
        "check_s": time.perf_counter() - t_check,
        "checked_requests": check.requests, "checked_tokens": check.tokens,
        **extra_trace,
    }
    return {"correct": ok, "attempted": len(reqs), "failed": failed,
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "run": extra, "checks": checks}


def emit(result: dict) -> None:
    """Numbers compared with their limits last on stderr; the result as the
    last line of stdout, its ``checks`` key last."""
    for k, v in result["run"].items():
        print(f"run {k}: {v}", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    out = {k: v for k, v in result.items()
           if k not in ("checks", "breakdown", "run") and v is not None}
    if result.get("breakdown") is not None:
        out["breakdown"] = result["breakdown"]
    out["run"] = result["run"]
    out["checks"] = result["checks"]
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
