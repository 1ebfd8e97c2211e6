"""MiniCPM's published function, served: a MiniCPM-shaped small config
(MHA, heads of 64, so the pools' rows are four heads of 64 side by side;
``scale_emb`` 12, ``scale_depth`` 1.4, ``dim_model_base`` 32, so the head
divides by 256 / 32 = 8) on the benchmark's seeded weights
(``bench/weights.py``), prefilled and decoded through the paged KV pools
by the scheduler's own steps, against the plain reference's full forward
pass (``bench/references/dense_decoder.py``); and the harness's tiny cell
at these widths."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_fixtures import add_cell

PUBLISHED = {"model_type": "minicpm", "hidden_size": 256, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 64, "intermediate_size": 512,
             "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
             "rope_theta": 10000.0, "scale_emb": 12, "scale_depth": 1.4,
             "dim_model_base": 32, "tie_word_embeddings": True, "hidden_act": "silu"}
OVERRIDES = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 4,
             "head_dim": 64, "d_ff": 512, "vocab_size": 512,
             "residual_scale": 1.4 / math.sqrt(2), "logit_divisor": 256 / 32,
             "attn_chunk": 16}
BS = 16
# the widest distance between a served logit and the reference's, over
# the spread of the reference's logits at that position.  The program
# holds weights, activations and K/V in bf16 (8 bits of mantissa): its
# logits read 0.008 here, the rounding grown through two layers and up to
# 60 positions.  K/V rounded to float8 e4m3 (3 bits) read 0.037, a head
# divided by 1 in place of 8 reads 4.3.  0.018 lies a factor of two from
# the program and from float8 K/V.
TOL = 0.018


def _program(c, overrides):
    from bench import harness
    from repro.launch.serve import parse_args, serve_config
    from repro.models import Model

    cfg = serve_config(parse_args(["--arch", "minicpm_2b"])).replace(**overrides)
    harness.published_widths_match(cfg, c)
    return Model(cfg)


def _served(model, params, prompt, new, kv_round=None):
    """Greedy tokens and their logits from the scheduler's paged steps, for
    one slot on its own blocks: the prefill step's logits at the prompt's
    last position, then the decode step's at each token it fed back;
    ``kv_round`` is applied to the pools after each step."""
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.runtime import PagedKVCache

    n = len(prompt)
    nb = -(-(n + new) // BS)
    kv = PagedKVCache(model.cfg, nb + 1, BS)
    pk, pv = kv.k, kv.v
    sp = max(BS, 1 << (n - 1).bit_length())
    padded = np.zeros((1, sp), np.int32)
    padded[0, :n] = prompt
    table = np.zeros((1, max(nb, sp // BS)), np.int32)
    table[0, :nb] = np.arange(1, nb + 1)
    prefill = jax.jit(make_prefill_step(model, paged=True))
    decode = jax.jit(make_decode_step(model, paged=True))
    rnd = kv_round or (lambda a: a)
    logits, pk, pv = prefill(params, {"tokens": jnp.asarray(padded)},
                             jnp.asarray([n], jnp.int32),
                             jnp.asarray(table[:, :sp // BS]), pk, pv)
    out, tokens = [logits[0]], [int(jnp.argmax(logits[0]))]
    for i in range(new - 1):
        pk, pv = rnd(pk), rnd(pv)
        logits, pk, pv = decode(params, pk, pv, jnp.asarray(table[:, :nb]),
                                jnp.asarray([n + i], jnp.int32),
                                jnp.asarray([tokens[-1]], jnp.int32))
        out.append(logits[0])
        tokens.append(int(jnp.argmax(logits[0])))
    return tokens, np.asarray(jnp.stack(out), np.float32)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


@pytest.mark.parametrize("variant", ["served", "no_head_divisor", "fp8_kv"])
def test_served_logits_against_the_reference(variant):
    """Every logit the paged steps produce, at the first token of each of
    three prompts and at each decode step after it, lies within ``TOL``
    (of the reference's spread) of the reference's logit; the two
    departures a sound program must not make lie outside it."""
    from bench import weights as W
    from bench.references.dense_decoder import make_forward
    from repro.models import Model

    model = _program(PUBLISHED, OVERRIDES)
    if variant == "no_head_divisor":
        model = Model(model.cfg.replace(logit_divisor=1.0))
    params = W.make(PUBLISHED, 7, qk_norm=False, tied=True)
    fwd = make_forward(PUBLISHED)
    rng = np.random.default_rng(3)
    worst = 0.0
    for n, new in ((9, 6), (20, 12), (37, 24)):
        prompt = rng.integers(0, PUBLISHED["vocab_size"], n).astype(np.int32)
        tokens, got = _served(model, params, prompt, new,
                              kv_round=_fp8 if variant == "fp8_kv" else None)
        seq = np.zeros(256, np.int32)
        seq[:n], seq[n:n + new] = prompt, tokens
        want = np.asarray(fwd(params, jnp.asarray(seq), n - 1, new))
        spread = want.max(-1) - want.min(-1)
        worst = max(worst, float((np.abs(got - want).max(-1) / spread).max()))
    if variant == "served":
        assert worst <= TOL, worst
    else:
        assert worst > TOL, worst


def test_tiny_minicpm_cell_is_correct(bench_copy):
    """The harness serves a cell of this configuration, added as files,
    through ``ServeScheduler``, and the reference finds it correct."""
    import time

    from bench import harness

    program = {"arch": "minicpm_2b", "flags": ["--pallas"], "path": "test"}
    add_cell(bench_copy, "tiny_mha.mini", "tiny_mha", dict(PUBLISHED, program=program),
             OVERRIDES,
             {"arrival": {"kind": "all_at_start"},
              "prompt_len": {"kind": "lognormal", "median": 20, "sigma": 0.5,
                             "min": 8, "max": 48},
              "output_len": {"kind": "uniform", "min": 2, "max": 6},
              "block_size": BS},
             {"slots": 2, "check_tokens": 12, "limits": {"max_logit_gap": 0.05}})
    r = harness.run("tiny_mha.mini", 11, 1.0, False, root=bench_copy,
                    t_start=time.perf_counter(), require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 2
