"""Continuous-batching serving: block allocator properties, paged-decode
equivalence, scheduler invariants, and the v6 bucketed plan-cache schema.

The scheduler's contract is deterministic serving: greedy token streams
bitwise identical to classic per-request ``prefill``/``decode_step``
decoding, independent of arrival order, co-scheduled batch composition,
and bucket padding.  These tests pin that contract, the paged KV cache's
allocator safety (no double-allocation, frees return, graceful exhaustion),
and the CMU side: decode sub-plans keyed on batch-size buckets survive a
save/load roundtrip, v5 caches migrate and upgrade incrementally without
touching their measured forward rows, and the pallas dispatch actually
consults the bucket plans."""

import json
import math
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core import (
    DECODE_BUCKETS,
    activate_plan,
    autotune_plan,
    decode_bucket,
    load_or_autotune,
    load_plan,
    model_epilogues,
    model_gemms,
    plan_matches,
    save_plan,
)
from repro.core.plan_cache import PLAN_CACHE_VERSION
from repro.core import cmu as cmu_mod
from repro.core.cmu import Dataflow, LayerPlan
from repro.launch.scheduler import (
    Request,
    RequestStatus,
    ServeScheduler,
    poisson_trace,
    run_fixed_batch,
    serve_buckets,
)
from repro.launch.serve import sequential_reference
from repro.models import Model, get_config
from repro.runtime import BlockAllocator, PagedKVCache, SCRATCH_BLOCK


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(num_blocks=st.integers(min_value=2, max_value=24),
       seed=st.integers(min_value=0, max_value=999))
def test_allocator_never_double_allocates(num_blocks, seed):
    """A random alloc/free interleaving: every live block id is unique,
    scratch is never handed out, frees return capacity, and the allocator
    ends empty when everything is freed."""
    rng = np.random.default_rng(seed)
    alloc = BlockAllocator(num_blocks)
    live: list[list[int]] = []
    seen_live: set[int] = set()
    for _ in range(40):
        if live and rng.random() < 0.4:
            blocks = live.pop(rng.integers(len(live)))
            alloc.free(blocks)
            seen_live -= set(blocks)
        else:
            n = int(rng.integers(1, max(2, num_blocks // 2)))
            got = alloc.alloc(n)
            if got is None:
                assert alloc.free_blocks < n  # refusal only when short
                continue
            assert len(got) == n
            assert SCRATCH_BLOCK not in got
            assert not (set(got) & seen_live), "block handed out twice"
            seen_live |= set(got)
            live.append(got)
        assert alloc.live_blocks == len(seen_live)
    for blocks in live:
        alloc.free(blocks)
    assert alloc.live_blocks == 0
    assert alloc.free_blocks == num_blocks - 1  # all but scratch


def test_allocator_exhaustion_returns_none_and_recovers():
    alloc = BlockAllocator(4)  # 3 usable
    a = alloc.alloc(2)
    assert a is not None and alloc.alloc(2) is None  # graceful, no raise
    b = alloc.alloc(1)
    assert b is not None and alloc.free_blocks == 0
    alloc.free(a)
    assert alloc.alloc(2) is not None


def test_allocator_rejects_foreign_and_double_free():
    alloc = BlockAllocator(4)
    a = alloc.alloc(2)
    alloc.free(a)
    with pytest.raises(ValueError):
        alloc.free(a)  # double free
    with pytest.raises(ValueError):
        alloc.free([SCRATCH_BLOCK])  # scratch is never owned


# ---------------------------------------------------------------------------
# bucket quantization
# ---------------------------------------------------------------------------


@settings(max_examples=16, deadline=None)
@given(m=st.integers(min_value=1, max_value=80))
def test_decode_bucket_is_smallest_fitting(m):
    b = decode_bucket(m)
    fitting = [x for x in DECODE_BUCKETS if m <= x]
    assert b == (min(fitting) if fitting else None)


def test_serve_buckets_caps_at_capacity():
    assert serve_buckets(8) == (8,)
    assert serve_buckets(16) == (8, 16)
    assert serve_buckets(12) == (8, 12)   # capacity itself is always a bucket
    assert serve_buckets(64) == (8, 16, 32, 64)


# ---------------------------------------------------------------------------
# scheduler vs classic sequential decode
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("qwen3_4b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _trace(cfg, n=8, rate=0.7, seed=11, max_prompt=14, max_gen=6):
    return poisson_trace(n, vocab=cfg.vocab_size, max_prompt=max_prompt,
                         max_gen=max_gen, rate=rate, seed=seed)


def test_scheduler_matches_sequential_reference(smoke_model):
    """Every admitted request finishes with exactly max_new tokens, all
    KV blocks return to the pool, and each stream is bitwise identical to
    classic per-request prefill/decode_step serving."""
    cfg, model, params = smoke_model
    trace = _trace(cfg)
    sched = ServeScheduler(model, params, capacity=8, block_size=16,
                           max_total_len=14 + 6)
    results, stats = sched.run(trace)
    assert set(results) == {r.rid for r in trace}
    assert stats.prefills == len(trace)
    assert sched.kv.allocator.live_blocks == 0
    ref = sequential_reference(model, params, trace,
                               sched.max_blocks * sched.block_size)
    for r in trace:
        got = results[r.rid]
        assert got.tokens is not None and len(got.tokens) == r.max_new
        assert got.admitted_step <= got.finished_step
        np.testing.assert_array_equal(got.tokens, ref[r.rid])


def test_streams_independent_of_batch_composition(smoke_model):
    """The same trace served at capacity 2 and capacity 8 co-schedules
    entirely different batches (and hits different buckets) — the token
    streams must not change."""
    cfg, model, params = smoke_model
    trace = _trace(cfg, seed=5)
    wide = ServeScheduler(model, params, capacity=8, block_size=16,
                          max_total_len=14 + 6).run(trace)[0]
    narrow = ServeScheduler(model, params, capacity=2, block_size=16,
                            max_total_len=14 + 6).run(trace)[0]
    for r in trace:
        np.testing.assert_array_equal(wide[r.rid].tokens, narrow[r.rid].tokens)


def test_streams_independent_of_arrival_order(smoke_model):
    cfg, model, params = smoke_model
    trace = _trace(cfg, seed=7)
    all_at_once = [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
                   for r in trace]
    a = ServeScheduler(model, params, capacity=8, block_size=16,
                       max_total_len=14 + 6).run(trace)[0]
    b = ServeScheduler(model, params, capacity=8, block_size=16,
                       max_total_len=14 + 6).run(all_at_once)[0]
    for r in trace:
        np.testing.assert_array_equal(a[r.rid].tokens, b[r.rid].tokens)


def test_scheduler_queues_gracefully_on_block_exhaustion(smoke_model):
    """A pool sized for ~2 concurrent requests forces later arrivals to
    FIFO-wait for evictions; everyone still finishes, correctly."""
    cfg, model, params = smoke_model
    trace = _trace(cfg, n=6, rate=0.0, seed=3)  # all arrive at step 0
    sched = ServeScheduler(model, params, capacity=8, block_size=16,
                           max_total_len=14 + 6,
                           num_blocks=3)  # 2 usable blocks + scratch
    results, stats = sched.run(trace)
    assert max(stats.active_per_step) <= 2  # the pool really was the limit
    assert max(stats.active_per_step) < len(trace)  # admission throttled
    assert sched.kv.allocator.live_blocks == 0
    ref = sequential_reference(model, params, trace,
                               sched.max_blocks * sched.block_size)
    for r in trace:
        np.testing.assert_array_equal(results[r.rid].tokens, ref[r.rid])


def test_oversized_request_rejected_up_front(smoke_model):
    """An inadmissible request (prompt + max_new exceeds the cache) gets a
    per-request REJECTED result instead of crashing the whole batch."""
    cfg, model, params = smoke_model
    sched = ServeScheduler(model, params, capacity=4, block_size=16,
                           max_total_len=32)
    huge = [Request(rid=0, prompt=np.zeros(30, np.int32), max_new=10)]
    results, stats = sched.run(huge)
    assert results[0].status is RequestStatus.REJECTED
    assert results[0].tokens is None
    assert stats.rejections == 1


def test_fixed_batch_baseline_same_model(smoke_model):
    """The legacy loop still serves: right answer count, one token stream
    per request at its own max_new."""
    cfg, model, params = smoke_model
    trace = _trace(cfg, n=4, seed=2)
    results, st_ = run_fixed_batch(model, params, trace)
    assert set(results) == {r.rid for r in trace}
    for r in trace:
        assert len(results[r.rid]) == r.max_new
    assert st_["row_steps"] == len(trace) * max(r.max_new for r in trace)


# ---------------------------------------------------------------------------
# plan cache v6: bucketed decode sub-plans
# ---------------------------------------------------------------------------


GEMMS = lambda cfg: model_gemms(cfg, tokens=64)  # noqa: E731


def test_v6_roundtrip_and_bucket_lookup(tmp_path):
    cfg = get_config("qwen3_4b", smoke=True).replace(use_pallas=True)
    plan = autotune_plan(GEMMS(cfg), measure=False, decode_buckets=(8, 16),
                         epilogue=model_epilogues(cfg))
    path = os.path.join(tmp_path, "plan.json")
    save_plan(path, plan)
    with open(path) as f:
        assert json.load(f)["version"] == PLAN_CACHE_VERSION
    plan2 = load_plan(path)
    assert plan2.has_decode((8, 16)) and not plan2.has_decode((8, 16, 32))
    assert plan_matches(plan2, GEMMS(cfg), buckets=(8, 16))
    assert not plan_matches(plan2, GEMMS(cfg), buckets=(8, 16, 32))
    for lp in plan2.layers:
        # lookup quantizes up: m=5 -> bucket 8; m=9 -> 16; m=17 -> None
        assert lp.decode_plan(5) == lp.decode[8]
        assert lp.decode_plan(9) == lp.decode[16]
        assert lp.decode_plan(17) is None


def test_v5_cache_loads_with_decode_none_and_upgrades(tmp_path):
    """A v5 file (no decode sub-plans) loads with decode=None; a bucketed
    load_or_autotune upgrades it incrementally — the measured forward rows
    survive verbatim and only the buckets are tuned."""
    cfg = get_config("qwen3_4b", smoke=True).replace(use_pallas=True)
    plan = autotune_plan(GEMMS(cfg), measure=False,
                         epilogue=model_epilogues(cfg))
    path = os.path.join(tmp_path, "plan.json")
    save_plan(path, plan)
    with open(path) as f:
        payload = json.load(f)
    payload["version"] = 5
    for row in payload["layers"]:
        row.pop("decode", None)
    with open(path, "w") as f:
        json.dump(payload, f)

    v5 = load_plan(path)
    assert all(lp.decode is None for lp in v5.layers)
    assert plan_matches(v5, GEMMS(cfg))          # bucketless request: fine
    assert not plan_matches(v5, GEMMS(cfg), buckets=(8,))

    before = {lp.name: (lp.dataflow, lp.block, lp.strip) for lp in v5.layers}
    up, loaded = load_or_autotune(path, GEMMS(cfg), buckets=(8,),
                                  measure=False,
                                  epilogue=model_epilogues(cfg))
    assert not loaded  # it had to tune (the buckets)
    assert up.has_decode((8,))
    for lp in up.layers:
        assert (lp.dataflow, lp.block, lp.strip) == before[lp.name], \
            "incremental bucket upgrade must not retune forward rows"
    # and the upgrade was persisted as the current schema version
    with open(path) as f:
        assert json.load(f)["version"] == PLAN_CACHE_VERSION
    again, loaded = load_or_autotune(path, GEMMS(cfg), buckets=(8,),
                                     measure=False)
    assert loaded  # second launch reloads, no tuning


def test_widening_slots_adds_only_missing_buckets(tmp_path):
    cfg = get_config("qwen3_4b", smoke=True).replace(use_pallas=True)
    plan = autotune_plan(GEMMS(cfg), measure=False, decode_buckets=(8,))
    path = os.path.join(tmp_path, "plan.json")
    save_plan(path, plan)
    before = {lp.name: lp.decode[8] for lp in plan.layers}
    up, loaded = load_or_autotune(path, GEMMS(cfg), buckets=(8, 16),
                                  measure=False)
    assert not loaded and up.has_decode((8, 16))
    for lp in up.layers:
        assert lp.decode[8] == before[lp.name], \
            "existing buckets must survive a widening verbatim"


def test_bucket_tuning_is_measurement_driven(monkeypatch):
    """Under a fake timer that penalizes whatever the analytical model would
    pick for each decode bucket, the measured sub-plan lands on a different
    (dataflow, block) — the bucket decisions come from the measurements, not
    from the analytical ranking or the forward dataflow."""
    from repro.core import hbm_traffic_bytes

    cfg = get_config("qwen3_4b", smoke=True).replace(use_pallas=True)
    analytic = autotune_plan(GEMMS(cfg), measure=False, decode_buckets=(8,))
    pick = {lp.name: (lp.decode[8].dataflow, lp.decode[8].block)
            for lp in analytic.layers}

    def fake(gemm, df, blk, **kw):
        base = hbm_traffic_bytes(gemm, df, *blk).time_s()
        # decode-tune GEMMs are named "<layer>@b<bucket>"
        name = gemm.name.split("@")[0]
        if "@b" in gemm.name and (df, blk) == pick[name]:
            return base * 100.0
        return base

    monkeypatch.setattr(cmu_mod, "measure_kernel", fake)
    plan = autotune_plan(GEMMS(cfg), measure=True, iters=1,
                         decode_buckets=(8,))
    for lp in plan.layers:
        got = (lp.decode[8].dataflow, lp.decode[8].block)
        assert got != pick[lp.name], lp.name
        assert lp.decode[8].source == "measured"


def test_paged_decode_dispatches_bucket_plan(smoke_model):
    """End to end on the pallas path: a scheduler run consults
    LayerPlan.decode_plan at decode-trace time, only with bucket-sized row
    counts, and its streams still match sequential decode."""
    cfg, _, _ = smoke_model
    cfg = cfg.replace(use_pallas=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    buckets = serve_buckets(4)
    plan = autotune_plan(model_gemms(cfg, tokens=64), measure=False,
                         decode_buckets=buckets,
                         epilogue=model_epilogues(cfg))
    activate_plan(plan)
    try:
        lookups = []
        orig = LayerPlan.decode_plan

        def recording(self, m):
            sub = orig(self, m)
            if sub is not None:
                lookups.append((self.name, m))
            return sub

        trace = _trace(cfg, n=4, max_prompt=10, max_gen=4, seed=1)
        sched = ServeScheduler(model, params, capacity=4, block_size=16,
                               max_total_len=10 + 4)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(LayerPlan, "decode_plan", recording)
            results, _ = sched.run(trace)
        assert lookups, "decode never consulted the bucket sub-plans"
        assert {m for _, m in lookups} <= set(buckets)
        ref = sequential_reference(model, params, trace,
                                   sched.max_blocks * sched.block_size)
        for r in trace:
            np.testing.assert_array_equal(results[r.rid].tokens, ref[r.rid])
    finally:
        activate_plan(None)


def test_scheduler_matches_sequential_with_pallas_attention():
    """Masking-contract regression, end to end: with the Pallas decode-
    attention path enabled (``attn_pallas``), bucket-pad rows are *fully
    masked* — the kernel must zero their probabilities multiplicatively
    (additive -1e30 bias alone leaves exp(0)=1 per dead key once a whole
    block is masked) so the scheduler's pad-row exact-zero guarantee still
    composes.  Pin stream-vs-sequential token equality for every bucket the
    capacities exercise, and that the Pallas kernel really dispatched."""
    import importlib

    # the package re-exports the flash_attention *function*, shadowing the
    # submodule attribute; import_module resolves the real module
    fa = importlib.import_module("repro.kernels.flash_attention")

    cfg = get_config("qwen3_4b", smoke=True).replace(use_pallas=True,
                                                     attn_pallas=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = _trace(cfg)
    calls = []
    orig = fa.paged_attention

    def recording(*args, **kw):
        calls.append(args[0].shape[0])  # decode batch (bucket) sizes
        return orig(*args, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "paged_attention", recording)
        ref = sequential_reference(model, params, trace, 14 + 6 + 12)
        for capacity in (2, 8):  # different co-scheduling -> buckets 2 and 8
            sched = ServeScheduler(model, params, capacity=capacity,
                                   block_size=16, max_total_len=14 + 6)
            results, _ = sched.run(trace)
            for r in trace:
                np.testing.assert_array_equal(results[r.rid].tokens,
                                              ref[r.rid])
    assert calls, "scheduler decode never dispatched the Pallas kernel"
    assert set(calls) <= set(serve_buckets(2)) | set(serve_buckets(8))


# ---------------------------------------------------------------------------
# paged KV cache pools
# ---------------------------------------------------------------------------


def _decode_sliced_oracle(model, params, pools, table, positions, token):
    """The paged decode step with each layer's pool sliced out of the
    stacked pools, handed to the block, and written back whole."""
    from repro.models.transformer import _group, block_decode_paged

    cfg = model.cfg
    x = params["embed"].astype(model.dtype)[token][:, None] * cfg.emb_scale
    pat = len(cfg.window_pattern)
    groups = cfg.num_layers // pat

    def gbody(carry, inp):
        x, pool_k, pool_v = carry
        lp, g = inp
        for j in range(pat):
            pj = jax.tree.map(lambda a, j=j: a[j], lp)
            li = g * pat + j
            kl = jax.lax.dynamic_index_in_dim(pool_k, li, 0, keepdims=False)
            vl = jax.lax.dynamic_index_in_dim(pool_v, li, 0, keepdims=False)
            x, kl, vl = block_decode_paged(cfg, pj, x, kl, vl, 0, table,
                                           positions,
                                           window=cfg.window_pattern[j])
            pool_k = jax.lax.dynamic_update_index_in_dim(pool_k, kl, li, 0)
            pool_v = jax.lax.dynamic_update_index_in_dim(pool_v, vl, li, 0)
        return (x, pool_k, pool_v), None

    (x, nk, nv), _ = jax.lax.scan(
        gbody, (x, pools["k"], pools["v"]),
        (_group(params["layers"], groups, pat), jnp.arange(groups)))
    return model._logits(params, x)[:, 0], {"k": nk, "v": nv}


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("arch", ["qwen3_4b", "gemma3_12b"])
def test_paged_decode_appends_into_the_stacked_pools_in_place(arch, path):
    """``decode_step_paged`` addresses each layer's blocks inside the
    stacked pools: the same logits and pools as slicing every layer's pool
    out and writing it back, with only each live slot's new row and the
    scratch blocks changed.  ``gemma3_12b`` runs two layers per scan group
    (a sliding window, then global); ``pallas`` reads the pools through the
    paged kernel instead of the gather."""
    cfg = get_config(arch, smoke=True).replace(attn_pallas=path == "pallas")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    bs, num_blocks = 16, 9
    shape = (cfg.num_layers, num_blocks, bs, cfg.kv_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    pools = {"k": jax.random.normal(kk, shape, jnp.bfloat16),
             "v": jax.random.normal(kv, shape, jnp.bfloat16)}
    # three live slots on blocks of their own, one pad slot on scratch
    table = jnp.array([[1, 2], [3, 4], [5, 6], [SCRATCH_BLOCK] * 2], jnp.int32)
    positions = jnp.array([5, 17, 30, 0], jnp.int32)
    token = jnp.array([3, 7, 11, 0], jnp.int32)

    logits, got = jax.jit(model.decode_step_paged)(
        params, pools, table, positions, token)
    want_logits, want = jax.jit(
        lambda *a: _decode_sliced_oracle(model, *a))(
        params, pools, table, positions, token)
    np.testing.assert_array_equal(logits, want_logits)
    live = [(int(table[b, p // bs]), p % bs)
            for b, p in enumerate(np.asarray(positions[:3]))]
    for n in ("k", "v"):
        np.testing.assert_array_equal(got[n], want[n])
        changed = np.asarray(got[n] != pools[n]).any(axis=3)
        allowed = np.zeros_like(changed)
        allowed[:, SCRATCH_BLOCK] = True
        for blk, off in live:
            assert changed[:, blk, off].all()  # every layer took the row
            allowed[:, blk, off] = True
        assert not (changed & ~allowed).any()


def _decode_head_layout_oracle(model, params, pools, table, positions, token):
    """The paged decode step on pools laid out ``(L, num_blocks, bs, Hkv,
    hd)``, a position's heads in a dimension of their own: each layer's
    pool sliced out, the new row appended and every slot's table gathered
    in that layout, then written back whole."""
    from repro.models import layers as Lyr
    from repro.models.transformer import _group

    cfg = model.cfg
    B, bs = token.shape[0], pools["k"].shape[2]
    x = params["embed"].astype(model.dtype)[token][:, None] * cfg.emb_scale

    def attention(p, h, pk, pv, window):
        q, k, v = Lyr._project_qkv(cfg, p, h)
        q = Lyr.rope(q, positions[:, None], cfg.rope_theta)
        k = Lyr.rope(k, positions[:, None], cfg.rope_theta)
        blk = table[jnp.arange(B), positions // bs]
        pk = pk.at[blk, positions % bs].set(k[:, 0].astype(pk.dtype))
        pv = pv.at[blk, positions % bs].set(v[:, 0].astype(pv.dtype))
        kd, vd = pk[table], pv[table]  # (B, nb, bs, Hkv, hd)
        o = Lyr._decode_core(q, kd.reshape(B, -1, cfg.kv_dim),
                             vd.reshape(B, -1, cfg.kv_dim),
                             jnp.arange(kd.shape[1] * bs), positions, window,
                             1.0 / math.sqrt(cfg.head_dim), None)
        return (Lyr.linear(cfg, o.reshape(B, 1, cfg.q_dim), p["wo"],
                           name="attn.wo"), pk, pv)

    def gbody(carry, lp):
        x, pool_k, pool_v = carry
        li, lp = lp
        kl = jax.lax.dynamic_index_in_dim(pool_k, li, 0, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(pool_v, li, 0, keepdims=False)
        h, kl, vl = attention(lp["attn"], Lyr.norm(cfg, lp["ln1"], x), kl, vl,
                              cfg.window_pattern[0])
        x = x + cfg.residual_scale * h
        x = x + cfg.residual_scale * Lyr.mlp(cfg, lp["mlp"],
                                             Lyr.norm(cfg, lp["ln2"], x))
        pool_k = jax.lax.dynamic_update_index_in_dim(pool_k, kl, li, 0)
        pool_v = jax.lax.dynamic_update_index_in_dim(pool_v, vl, li, 0)
        return (x, pool_k, pool_v), None

    layers = jax.tree.map(lambda a: a[:, 0], _group(params["layers"], cfg.num_layers, 1))
    (x, nk, nv), _ = jax.lax.scan(
        gbody, (x, pools["k"], pools["v"]), (jnp.arange(cfg.num_layers), layers))
    return model._logits(params, x)[:, 0], {"k": nk, "v": nv}


HEAD_SIZES = {"hd64": ("minicpm_2b", 64), "hd128": ("qwen3_4b", 128)}


@pytest.mark.parametrize("heads", list(HEAD_SIZES))
def test_pool_rows_hold_the_head_layout(heads):
    """A pool row is a position's heads side by side: the prefill's scatter,
    the decode step's append and its gather give bitwise the pool contents
    and logits of pools laid out ``(…, Hkv, hd)``, at heads of 64 (MHA) and
    of 128 (GQA)."""
    from repro.runtime import write_prefill_blocks

    arch, hd = HEAD_SIZES[heads]
    cfg = get_config(arch, smoke=True).replace(head_dim=hd)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    L, bs, num_blocks = cfg.num_layers, 16, 9
    heads5 = (L, num_blocks, bs, cfg.num_kv_heads, hd)
    flat = (L, num_blocks, bs, cfg.kv_dim)
    # a prefill of two prompts of 32 positions into blocks 1-4
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab_size)
    ptable = jnp.array([[1, 2], [3, 4]], jnp.int32)
    _, k_all, v_all = model.prefill_kv(params, {"tokens": tokens})
    zero = jnp.zeros(flat, jnp.bfloat16)
    pk, pv = write_prefill_blocks(zero, zero, k_all, v_all, ptable)
    k5 = k_all.reshape(L, 2, 2, bs, cfg.num_kv_heads, hd)
    want_k = jnp.zeros(heads5, jnp.bfloat16).at[:, ptable].set(k5)
    np.testing.assert_array_equal(pk.reshape(heads5), want_k)
    # then one decode step of those two slots and a third, and a pad slot
    kk = jax.random.PRNGKey(1)
    pools = {"k": pk.at[:, 5:].set(jax.random.normal(kk, (L, 4, bs, cfg.kv_dim),
                                                     jnp.bfloat16)),
             "v": pv.at[:, 5:].set(-1.0)}
    table = jnp.array([[1, 2, 7], [3, 4, 8], [5, 6, SCRATCH_BLOCK],
                       [SCRATCH_BLOCK] * 3], jnp.int32)
    positions = jnp.array([32, 20, 17, 0], jnp.int32)
    token = jnp.array([3, 7, 11, 0], jnp.int32)
    logits, got = jax.jit(model.decode_step_paged)(
        params, pools, table, positions, token)
    want_logits, want = jax.jit(
        lambda *a: _decode_head_layout_oracle(model, *a))(
        params, {n: a.reshape(heads5) for n, a in pools.items()}, table,
        positions, token)
    np.testing.assert_array_equal(logits, want_logits)
    for n in ("k", "v"):
        np.testing.assert_array_equal(got[n].reshape(heads5), want[n])


@pytest.mark.parametrize("heads", list(HEAD_SIZES))
def test_serve_stats_count_the_pool_bytes(heads):
    """``ServeStats`` holds the pools' device bytes and positions as the
    scheduler built them: on the CPU, with no padding, K and V of every
    layer at every position, 2 bytes an element."""
    arch, hd = HEAD_SIZES[heads]
    cfg = get_config(arch, smoke=True).replace(head_dim=hd)
    model = Model(cfg)
    sched = ServeScheduler(model, model.init(jax.random.PRNGKey(0)), capacity=2,
                           block_size=16, max_total_len=32, num_blocks=9)
    _, stats = sched.run(_trace(cfg, n=2))
    assert stats.kv_pool_positions == 9 * 16
    assert stats.kv_pool_bytes == (cfg.num_layers * 2 * cfg.num_kv_heads * hd
                                   * 2 * stats.kv_pool_positions)


def test_paged_cache_geometry(smoke_model):
    cfg, _, _ = smoke_model
    kv = PagedKVCache(cfg, num_blocks=6, block_size=16)
    assert kv.k.shape == (cfg.num_layers, 6, 16, cfg.num_kv_heads * cfg.head_dim)
    assert kv.k.dtype == jnp.bfloat16
    assert kv.blocks_for(1) == 1 and kv.blocks_for(16) == 1
    assert kv.blocks_for(17) == 2
    blocks = kv.alloc(33)
    assert blocks is not None and len(blocks) == 3
    kv.free(blocks)
    assert kv.allocator.live_blocks == 0
