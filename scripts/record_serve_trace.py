"""Record a small serve trace on one TPU, for the benchmark's CPU tests.

    python scripts/record_serve_trace.py --out chiprun_out/trace      # on a TPU
    python scripts/record_serve_trace.py --from-raw chiprun_out/trace/raw.json.gz \
        --fixture tests/bench/data/serve_scopes_v5e.json.gz           # anywhere

On a TPU it serves qwen3_4b at its published widths cut to 2 layers (tied
head, flex GEMMs, 4 slots; 8 requests of 300-700 prompt tokens and 8 new
tokens each, all queued at once) once to compile, then again under the
profiler, and writes ``raw.json.gz``: every event of the device plane and
every ``serve.*`` / ``bench.*`` annotation of the host planes, each with
all its stats, the scheduler's ``ServeStats`` spans and events, the
``perf_counter`` reading inside ``bench.anchor``, the compiled steps' HLO
text, and the host cost of one ``ServeStats.span`` with the profiler off
and on.  It exits non-zero without a TPU.

``--from-raw`` cuts a window of a raw recording into the test fixture:
``--ms`` milliseconds from ``--start-ms`` after ``serve.run`` began, the
device lines (event names and times) and the host annotations (with
their ``step`` and ``rid``), the scheduler's spans and events, and each
program kind's ``bench.scope_reduce.op_names`` from the HLO texts.  Names
and stat values are interned.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SOURCE = ("one TPU v5e (device_kind 'TPU v5 lite'): qwen3_4b at published widths "
          "cut to 2 layers, tied head, flex GEMMs, 4 slots, 8 requests of 300-700 "
          "prompt tokens and 8 new tokens each; the profiler's trace of "
          "ServeScheduler.run, {start:g}-{end:g} ms after it started: device lines, "
          "the host's bench.anchor and serve.* annotations, the scheduler's spans "
          "and events, and the op_names of the compiled programs' instructions")


def _events(line, keep):
    return [[ev.name, ev.start_ns, ev.duration_ns,
             [[k, v if isinstance(v, (int, float, str)) else str(v)] for k, v in ev.stats]]
            for ev in line.events if keep(ev.name)]


def record(out: Path) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import profiler
    from jax.profiler import ProfileData

    from repro.launch import serve
    from repro.launch.scheduler import Request, ServeScheduler, ServeStats
    from repro.models import Model

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {dev.platform}")
    cfg = serve.serve_config(serve.parse_args(["--arch", "qwen3_4b", "--pallas"])
                             ).replace(num_layers=2, tie_embeddings=True)
    model = Model(cfg)
    params = serve.init_params(model)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, int(n), np.int32),
                    max_new=8) for i, n in enumerate(rng.integers(300, 701, 8))]
    sched = ServeScheduler(model, params, capacity=4, block_size=16,
                           max_total_len=1024)
    sched.run(reqs)  # compiles every prompt and batch bucket

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    def stop():
        with profiler._profile_state.lock:
            xs = profiler._profile_state.profile_session.stop()
            profiler._profile_state.reset()
        return ProfileData.from_serialized_xspace(xs)

    out.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.anchor"):
        anchor = time.perf_counter()
    _, stats = sched.run(reqs)
    pd = stop()

    planes = []
    for p in pd.planes:
        if p.name.startswith("/device:TPU:0"):
            lines = [{"name": ln.name, "events": _events(ln, lambda n: True)}
                     for ln in p.lines]
        elif p.name.startswith("/host"):
            lines = [{"name": ln.name, "events": _events(
                ln, lambda n: n.startswith(("serve.", "bench.")))} for ln in p.lines]
            lines = [ln for ln in lines if ln["events"]]
        else:
            continue
        planes.append({"name": p.name, "lines": lines})

    # the HLO text of every program the trace ran (prompt buckets 512 and
    # 1024, the one batch bucket), op_name metadata included
    pf, dc = sched._prefill, sched._decode
    kv = jax.ShapeDtypeStruct(sched.kv.k.shape, sched.kv.k.dtype)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    hlo = {
        "decode": [dc.lower(params, kv, kv, i32(4, sched.max_blocks), i32(4), i32(4),
                            jax.ShapeDtypeStruct((4,), bool)).compile().as_text()],
        "prefill": [pf.lower(params, i32(1, n), i32(1), i32(1, n // 16), kv, kv
                             ).compile().as_text() for n in (512, 1024)],
    }

    def span_us(n: int = 20000) -> float:
        st = ServeStats(capacity=1)
        t0 = time.perf_counter()
        for i in range(n):
            with st.span("serve.decode", step=i):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    cost = {"off": span_us()}
    jax.profiler.start_trace(str(out), profiler_options=opts)
    cost["on"] = span_us()
    stop()
    cost["off_again"] = span_us()

    raw = {"device_kind": dev.device_kind, "anchor": anchor, "planes": planes,
           "spans": stats.spans, "events": stats.events, "hlo": hlo,
           "span_cost_us": cost}
    (out / "raw.json.gz").write_bytes(gzip.compress(json.dumps(raw).encode()))
    print(json.dumps({"span_cost_us": cost, "spans": len(stats.spans),
                      "planes": [(p["name"], [(ln["name"], len(ln["events"]))
                                              for ln in p["lines"]]) for p in planes]}))


def cut(raw_path: Path, fixture: Path, start_ms: float, ms: float) -> None:
    """The test fixture from a raw recording (see the module docstring)."""
    sys.path.insert(0, str(ROOT))
    from bench.scope_reduce import op_names

    raw = json.loads(gzip.decompress(raw_path.read_bytes()))
    host = [ev for p in raw["planes"] if p["name"].startswith("/host")
            for ln in p["lines"] for ev in ln["events"]]
    run = next(ev for ev in host if ev[0] == "serve.run")
    w0, w1 = run[1] + start_ms * 1e6, run[1] + (start_ms + ms) * 1e6
    values: dict = {}

    def idx(v) -> int:
        return values.setdefault((type(v).__name__, v), len(values))

    def pack(ev, keep: tuple[str, ...]):
        name, s, d, st = ev
        kept = [x for k, v in st if k in keep for x in (idx(k), idx(v))]
        return [idx(name), s, d] + ([kept] if kept else [])

    planes = []
    for p in raw["planes"]:
        device = p["name"].startswith("/device")
        lines = []
        for ln in p["lines"]:
            evs = [pack(ev, () if device else ("step", "rid")) for ev in ln["events"]
                   if (ev[1] < w1 and ev[1] + ev[2] > w0) or ev[0] == "bench.anchor"]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        planes.append({"name": p["name"], "lines": lines})
    out = {"source": SOURCE.format(start=start_ms, end=start_ms + ms),
           "values": [v for _, v in values], "planes": planes, "anchor": raw["anchor"],
           "spans": raw["spans"], "events": raw["events"],
           "op_names": {k: op_names(*v) for k, v in raw["hlo"].items()}}
    fixture.write_bytes(gzip.compress(json.dumps(out, separators=(",", ":")).encode(),
                                      mtime=0))
    print(f"{fixture}: {fixture.stat().st_size} bytes")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, help="record on the TPU into this directory")
    ap.add_argument("--from-raw", type=Path, help="a raw.json.gz to cut")
    ap.add_argument("--fixture", type=Path, help="the fixture to write")
    ap.add_argument("--start-ms", type=float, default=40.0)
    ap.add_argument("--ms", type=float, default=70.0)
    args = ap.parse_args(argv)
    if args.out is not None:
        record(args.out)
    elif args.from_raw is not None and args.fixture is not None:
        cut(args.from_raw, args.fixture, args.start_ms, args.ms)
    else:
        ap.error("give --out, or --from-raw and --fixture")
    return 0


if __name__ == "__main__":
    sys.exit(main())
