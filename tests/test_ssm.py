"""Chunked linear-attention (Mamba2 / RWKV-6) vs exact sequential recurrence."""

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.models import ssm as S

RNG = np.random.default_rng(7)

def _seq_ref(r, k, v, lw, post, u=None):
    B, T, H, N = r.shape
    M = v.shape[-1]
    St = jnp.zeros((B, H, N, M))
    outs = []
    for t in range(T):
        o, St = S.recurrent_step(
            r[:, t], k[:, t], v[:, t], lw[:, t], St, diag_scale=u, post_update=post
        )
        outs.append(o)
    return jnp.stack(outs, 1), St

def _inputs(B, T, H, N, M, seed=0):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.normal(size=(B, T, H, N)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, N)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, M)), jnp.float32)
    lw = jnp.clip(
        jnp.asarray(-np.abs(rng.normal(size=(B, T, H, N))), jnp.float32),
        S.LOG_DECAY_MIN, -1e-6,
    )
    return r, k, v, lw

@pytest.mark.parametrize("post", [True, False])
@pytest.mark.parametrize("T", [16, 32, 48])
def test_chunked_equals_recurrent(post, T):
    B, H, N, M = 2, 3, 8, 16
    r, k, v, lw = _inputs(B, T, H, N, M)
    u = jnp.asarray(RNG.normal(size=(H, N)), jnp.float32) if not post else None
    o_c, S_c = S.chunked_diag_linear_attn(r, k, v, lw, u, post_update=post)
    o_r, S_r = _seq_ref(r, k, v, lw, post, u)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(S_c), np.asarray(S_r), atol=2e-4, rtol=2e-4)

@given(seed=st.integers(0, 10_000), post=st.booleans())
@settings(max_examples=20, deadline=None)
def test_chunked_equals_recurrent_property(seed, post):
    B, T, H, N, M = 1, 32, 2, 4, 8
    r, k, v, lw = _inputs(B, T, H, N, M, seed)
    o_c, S_c = S.chunked_diag_linear_attn(r, k, v, lw, None, post_update=post)
    o_r, S_r = _seq_ref(r, k, v, lw, post, None)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r), atol=3e-4, rtol=3e-4)

def test_state_carried_across_calls():
    """Splitting a sequence across two chunked calls == one call (streaming)."""
    B, T, H, N, M = 1, 64, 2, 4, 8
    r, k, v, lw = _inputs(B, T, H, N, M, 3)
    o_full, S_full = S.chunked_diag_linear_attn(r, k, v, lw, post_update=True)
    h = T // 2
    o1, S1 = S.chunked_diag_linear_attn(
        r[:, :h], k[:, :h], v[:, :h], lw[:, :h], post_update=True
    )
    o2, S2 = S.chunked_diag_linear_attn(
        r[:, h:], k[:, h:], v[:, h:], lw[:, h:], state0=S1, post_update=True
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([o1, o2], 1)), np.asarray(o_full), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S_full), atol=2e-4, rtol=2e-4)

def test_numerical_safety_extreme_decay():
    """All exponents stay bounded at the decay floor — no inf/nan."""
    B, T, H, N, M = 1, 64, 1, 4, 4
    r, k, v, _ = _inputs(B, T, H, N, M, 5)
    lw = jnp.full((B, T, H, N), S.LOG_DECAY_MIN)
    o, St = S.chunked_diag_linear_attn(r, k, v, lw, post_update=True)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(St)))

def test_causal_conv_state_streaming():
    from repro.models.ssm import _causal_conv1d

    B, T, C, Kw = 2, 10, 6, 4
    x = jnp.asarray(RNG.normal(size=(B, T, C)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(Kw, C)), jnp.float32)
    b = jnp.zeros((C,))
    y_full, st_full = _causal_conv1d(x, w, b)
    # stream one token at a time
    st = jnp.zeros((B, Kw - 1, C))
    ys = []
    for t in range(T):
        y, st = _causal_conv1d(x[:, t : t + 1], w, b, state=st)
        ys.append(y)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, 1)), np.asarray(y_full), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_full), atol=1e-6)

def test_mamba2_block_shapes_and_decode():
    from repro.models.config import ModelConfig
    from repro.models.ssm import init_mamba2, init_mamba_state, mamba2

    cfg = ModelConfig(d_model=32, ssm_state=8, ssm_head_dim=8, num_heads=2, num_kv_heads=2)
    p = init_mamba2(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.normal(size=(2, 24, 32)), jnp.float32)
    y, _ = mamba2(cfg, p, x)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
    st = init_mamba_state(cfg, 2)
    y1, st = mamba2(cfg, p, x[:, :1], state=st)
    assert y1.shape == (2, 1, 32)


# ---------------------------------------------------------------------------
# prefill -> decode handoff: the state return_state captures is the state
# a decode stream actually needs (the contract the deleted duplicate-compute
# paths used to re-derive by running every layer twice)
# ---------------------------------------------------------------------------


@given(
    T=st.sampled_from([7, 16, 19, 32, 45]),  # ragged + aligned pad paths
    post=st.booleans(),
    dtype_name=st.sampled_from(["float32", "bfloat16"]),
)
@settings(max_examples=10, deadline=None)
def test_prefill_state_feeds_decode_exactly(T, post, dtype_name):
    """Chunked-prefill final state handed to ``recurrent_step`` continues
    the sequence identically to a full sequential decode — both
    conventions, ragged T (exercising the zero-pad path), both dtypes."""
    dtype = jnp.dtype(dtype_name)
    B, H, N, M = 1, 2, 4, 8
    extra = 4
    r, k, v, lw = _inputs(B, T + extra, H, N, M, seed=T)
    r, k, v = (a.astype(dtype).astype(jnp.float32) for a in (r, k, v))
    u = jnp.asarray(RNG.normal(size=(H, N)), jnp.float32) if not post else None
    # chunked prefill over the ragged prefix (pads internally to LA_CHUNK)
    pad = (-T) % S.LA_CHUNK
    rp, kp, vp, lwp = (S._pad_chunks(a[:, :T], pad) for a in (r, k, v, lw))
    _, St = S.chunked_diag_linear_attn(rp, kp, vp, lwp, u, post_update=post)
    # ... then decode the suffix from that state
    outs = []
    for t in range(T, T + extra):
        o, St = S.recurrent_step(r[:, t], k[:, t], v[:, t], lw[:, t], St,
                                 diag_scale=u, post_update=post)
        outs.append(o)
    # oracle: sequential decode of the whole sequence
    o_ref, S_ref = _seq_ref(r, k, v, lw, post, u)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(o_ref[:, T:]),
        atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(St), np.asarray(S_ref),
                               atol=3e-4, rtol=3e-4)


@given(T=st.sampled_from([5, 12, 16, 23]), post=st.booleans())
@settings(max_examples=8, deadline=None)
def test_pad_invariance_of_chunked_scan(T, post):
    """Output and final state are bitwise invariant to ``T % LA_CHUNK``:
    padding with zero rows (r = k = v = 0, log_w = 0) is an exact no-op.
    This is the property that made the historical ``where(lw == 0, -1e-6)``
    guard dead — and what lets the planner choose arbitrary chunks."""
    B, H, N, M = 1, 2, 4, 8
    r, k, v, lw = _inputs(B, T, H, N, M, seed=T * 7)
    pad = (-T) % S.LA_CHUNK
    a = [S._pad_chunks(x, pad) for x in (r, k, v, lw)]
    b = [S._pad_chunks(x, pad + 2 * S.LA_CHUNK) for x in (r, k, v, lw)]
    o_a, S_a = S.chunked_diag_linear_attn(*a, post_update=post)
    o_b, S_b = S.chunked_diag_linear_attn(*b, post_update=post)
    assert np.asarray(o_a[:, :T]).tobytes() == np.asarray(o_b[:, :T]).tobytes()
    assert np.asarray(S_a).tobytes() == np.asarray(S_b).tobytes(), \
        "final state depends on the pad amount"


def test_mamba2_return_state_matches_streaming_decode():
    """The state ``return_state=True`` captures during a chunked prefill is
    the state a token-by-token decode of the same prefix arrives at — the
    contract the deleted ``_mamba_final_state`` re-computed every layer to
    satisfy."""
    from repro.models.config import ModelConfig
    from repro.models.ssm import init_mamba2, init_mamba_state, mamba2

    cfg = ModelConfig(d_model=32, ssm_state=8, ssm_head_dim=8, num_heads=2,
                      num_kv_heads=2)
    p = init_mamba2(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.normal(size=(2, 24, 32)), jnp.float32)
    _, st_prefill = mamba2(cfg, p, x, return_state=True)
    st = init_mamba_state(cfg, 2)
    for t in range(x.shape[1]):
        _, st = mamba2(cfg, p, x[:, t : t + 1], state=st)
    np.testing.assert_allclose(np.asarray(st_prefill["ssm"]),
                               np.asarray(st["ssm"]), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st_prefill["conv"]),
                               np.asarray(st["conv"]), atol=1e-5, rtol=1e-5)


def _count_scan_cumsums(jaxpr):
    """Multi-dim cumsum ops anywhere in the jaxpr — the chunked scan's
    signature op (the 1-D bookkeeping cumsum in hybrid prefill is excluded
    by the ndim bar)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cumsum" and eqn.invars[0].aval.ndim >= 2:
            n += 1
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for sub in vals:
                if isinstance(sub, ClosedJaxpr):
                    n += _count_scan_cumsums(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    n += _count_scan_cumsums(sub)
    return n


@pytest.mark.parametrize("arch", ["zamba2_7b", "rwkv6_7b"])
def test_prefill_runs_one_chunked_scan_per_layer(arch):
    """Op-count regression for the prefill double-compute bug: prefill must
    trace exactly as many chunked scans as the forward pass (one per mixer
    body).  The old ``_mamba_final_state`` / inlined-rwkv paths re-ran
    every mixer a second time just to recover its final state."""
    from repro.models import get_config
    from repro.models.transformer import Model

    cfg = get_config(arch, smoke=True)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                      cfg.vocab_size)}
    n_fwd = _count_scan_cumsums(
        jax.make_jaxpr(lambda p, bb: m.forward(p, bb))(params, b).jaxpr)
    n_pre = _count_scan_cumsums(
        jax.make_jaxpr(lambda p, bb: m.prefill(p, bb, cache_len=16))(
            params, b).jaxpr)
    assert n_fwd >= 1  # detector sanity: the scan is visible
    assert n_pre == n_fwd, \
        f"prefill traces {n_pre} chunked scans but forward traces {n_fwd}"


@pytest.mark.parametrize("arch", ["zamba2_7b", "rwkv6_7b"])
def test_prefill_logits_bitwise_match_forward(arch):
    """Prefill runs the exact block-forward op sequence (plus state
    capture), so its last-position logits equal the forward pass *bitwise*
    — the pin that keeps the prefill paths from drifting back into
    hand-inlined near-copies."""
    from repro.models import get_config
    from repro.models.transformer import Model

    cfg = get_config(arch, smoke=True)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                      cfg.vocab_size)}
    full, _ = m.forward(params, b)
    _, last = m.prefill(params, b, cache_len=16)
    assert np.asarray(last).tobytes() == np.asarray(full[:, -1]).tobytes()


def test_rwkv_groupnorm_eps_derivation():
    """The group-norm eps derives from the head size (upstream RWKV's
    ``1e-5 * head_size_divisor**2``): 64e-5 at the stock 64, and it scales
    linearly — no more magic constant hardcoded at two call sites."""
    from repro.models.config import ModelConfig

    assert S.rwkv_groupnorm_eps(
        ModelConfig(d_model=64, rwkv_head_size=64)) == pytest.approx(64e-5)
    assert S.rwkv_groupnorm_eps(
        ModelConfig(d_model=64, rwkv_head_size=16)) == pytest.approx(16e-5)
