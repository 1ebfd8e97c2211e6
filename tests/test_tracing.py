"""The scheduler's host spans and the model step's named scopes.

``ServeStats.spans`` holds one ``serve.*`` span per piece of host work,
nested under ``serve.run`` and each also a profiler annotation; the
compiled decode and prefill steps carry every named scope in their HLO
``op_name`` metadata, and the scopes change nothing else."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.scheduler import Request, ServeScheduler, ServeStats, _jit_steps
from repro.models import Model, get_config
from repro.runtime import PagedKVCache

CHILDREN = ("serve.admit", "serve.decode", "serve.sync", "serve.drain")
PROJECTIONS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
               "mlp.w1", "mlp.w2", "mlp.w3", "lm_head")
SCOPES = {
    "decode": PROJECTIONS + ("kv_pool.read", "kv_pool.write", "kv.append",
                             "attn.kv_gather", "attn.core"),
    "prefill": PROJECTIONS + ("attn.core", "kv.scatter"),
}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3_4b", smoke=True)
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg, n=5):
    rng = np.random.default_rng(3)
    return [Request(rid=10 + i, prompt=rng.integers(0, cfg.vocab_size, 5 + 3 * i,
                                                    dtype=np.int32),
                    max_new=2 + i, arrival=i) for i in range(n)]


def _serve(tiny, reqs):
    cfg, model, params = tiny
    sched = ServeScheduler(model, params, capacity=2, block_size=16,
                           max_total_len=32)
    return sched.run(reqs)


def test_run_records_one_span_per_piece_of_host_work(tiny):
    reqs = _requests(tiny[0])
    results, stats = _serve(tiny, reqs)
    by_name: dict[str, list] = {}
    for s in stats.spans:
        by_name.setdefault(s[0], []).append(s)
    assert set(by_name) == {"serve.run", *CHILDREN}
    (run,) = by_name["serve.run"]
    assert len(by_name["serve.drain"]) == 1
    admits = by_name["serve.admit"]
    assert len(admits) == stats.prefills == len(reqs)
    assert sorted(s[4] for s in admits) == sorted(r.rid for r in reqs)
    assert [s[3] for s in by_name["serve.decode"]] == list(range(stats.steps))
    assert all(s[4] == -1 for s in by_name["serve.decode"] + by_name["serve.sync"])
    # one sync per event, at the decode steps the event counts
    assert [s[3] for s in by_name["serve.sync"]] == [e[0] for e in stats.events]
    children = sorted((s for s in stats.spans if s[0] != "serve.run"),
                      key=lambda s: s[1])
    assert all(run[1] <= s[1] <= s[2] <= run[2] for s in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    host = stats.host_seconds()
    assert set(host) == {*CHILDREN, "bookkeeping"} and host["bookkeeping"] >= 0
    assert sum(host.values()) == pytest.approx(run[2] - run[1])


def test_spans_are_host_annotations_of_a_profiler_trace(tiny, tmp_path):
    from jax.profiler import ProfileData

    reqs = _requests(tiny[0], n=3)
    _serve(tiny, reqs)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _, stats = _serve(tiny, reqs)
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    got = sorted((ev.name, dict(ev.stats)["step"], dict(ev.stats)["rid"])
                 for p in pd.planes if p.name.startswith("/host")
                 for ln in p.lines for ev in ln.events if ev.name.startswith("serve."))
    assert got == sorted((n, step, rid) for n, _, _, step, rid in stats.spans)


def _lowered(cfg, kind: str):
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefill, decode = _jit_steps(model)
    kv = jax.eval_shape(lambda: PagedKVCache(cfg, 9, 16).k)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if kind == "decode":
        lowered = decode.lower(params, kv, kv, i32(4, 4), i32(4), i32(4),
                               jax.ShapeDtypeStruct((4,), bool))
    else:
        lowered = prefill.lower(params, i32(1, 32), i32(1), i32(1, 2), kv, kv)
    return lowered


def _compiled_text(cfg, kind: str) -> str:
    return _lowered(cfg, kind).compile().as_text()


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_compiled_steps_carry_every_scope(tiny, kind, path):
    cfg = tiny[0].replace(use_pallas=path == "pallas")
    names = set(re.findall(r'op_name="([^"]*)"', _compiled_text(cfg, kind)))
    parts = {p for n in names for p in n.split("/")}
    missing = [s for s in SCOPES[kind] if s not in parts]
    assert not missing, missing
    # each projection names the path it took: the kernel's dispatched
    # dataflow under its jit, or the XLA path
    child = (r"jit\(flex_linear\)/(is|os|ws)/" if path == "pallas" else r"xla/")
    for proj in PROJECTIONS:
        assert any(re.search(rf"(^|/){re.escape(proj)}/{child}", n) for n in names), proj
    # the layer's weight slabs: at these sizes the CPU compiler fuses the
    # slice into the projection that reads it, so look before optimization
    lowered = _lowered(cfg, kind).as_text(debug_info=True)
    assert re.search(r'loc\("[^"]*weights/squeeze', lowered)


def _instructions(text: str) -> str:
    """The instructions of an HLO module, without metadata and with the
    numbers XLA appends to names dropped."""
    lines = [ln for ln in text.splitlines()
             if ln.lstrip().startswith(("%", "ROOT", "ENTRY"))]
    out = "\n".join(re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in lines)
    return re.sub(r"([%\w\-]+)\.\d+\b", r"\1", out)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_scopes_change_metadata_only(tiny, kind, monkeypatch):
    scoped = _compiled_text(tiny[0], kind)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_text(tiny[0], kind)
    assert "attn.core" in scoped and "attn.core" not in plain
    assert _instructions(scoped) == _instructions(plain)


def test_span_records_on_exit_and_on_error():
    stats = ServeStats(capacity=1)
    with stats.span("serve.decode", step=4):
        pass
    with pytest.raises(ValueError), stats.span("serve.admit", step=4, rid=7):
        raise ValueError
    assert [(n, step, rid) for n, _, _, step, rid in stats.spans] == [
        ("serve.decode", 4, -1), ("serve.admit", 4, 7)]
    assert all(t0 <= t1 for _, t0, t1, _, _ in stats.spans)
