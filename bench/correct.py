"""Whether the served tokens are right: the plain reference's verdict.

After the window has closed and the program's state is freed, a sample of
the finished requests is drawn from the seed, the request with the most
served tokens always among them, until it holds ``check_tokens`` served
tokens.  The reference runs once over each sampled prompt followed by its
served tokens, and reads, at each served token, by how much the
reference's logit of that token lies below the reference's best logit.
The number compared is the widest such gap over the sample.  A greedy
token that the reference also ranks first has a gap of 0; rounding moves
a token only among near ties, so a sound run's gaps stay small, and a
wrong token lies about the logits' own spread below the best.

``control`` reads the same positions with a lower-precision forward in
the program's place: the gap of the token that it ranks first.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

ROWS = 512  # the reference's sequence lengths are padded to multiples of this


@dataclass(frozen=True)
class Check:
    max_gap: float
    requests: int
    tokens: int
    control_gap: float | None = None


def sample(requests, served: dict, seed: int, target: int) -> list:
    """The longest served request, then others in an order drawn from the
    seed, until ``target`` served tokens are in the sample."""
    done = [r for r in requests if r.rid in served]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(served[r.rid]), len(r.prompt), -r.rid))
    rest = [r for r in done if r.rid != longest.rid]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, tokens = [longest], len(served[longest.rid])
    for i in order:
        if tokens >= target:
            break
        out.append(rest[i])
        tokens += len(served[rest[i].rid])
    return out


def _gaps(logits, picked):
    """Per row, the best logit less the logit of the picked token."""
    import jax.numpy as jnp

    best = logits.max(-1)
    return best - jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]


def _control_gaps(ref, control):
    """Per row, the reference's gap of the token the control ranks first."""
    import jax.numpy as jnp

    return _gaps(ref, jnp.argmax(control, -1).astype(jnp.int32))


def readings(fwd, params, prompt: np.ndarray, toks: np.ndarray, vocab: int,
             control=None):
    """Per served token, the gap under the reference ``fwd``; with
    ``control``, also the gap of the token the control ranks first."""
    import jax
    import jax.numpy as jnp

    n, p = len(toks), len(prompt)
    if n == 0:
        return np.zeros(0), None
    n_pad = max(16, 1 << (n - 1).bit_length())
    T = -(-(p - 1 + n_pad) // ROWS) * ROWS
    seq = np.zeros(T, np.int32)
    seq[:p] = prompt
    seq[p:p + n - 1] = toks[:-1]
    seq_d = jnp.asarray(seq)
    ref = fwd(params, seq_d, p - 1, n=n_pad)
    picked = np.where(toks < vocab, toks, 0).astype(np.int32)
    picked = np.concatenate([picked, np.zeros(n_pad - n, np.int32)])
    gaps = np.asarray(jax.device_get(jax.jit(_gaps)(ref, jnp.asarray(picked))))[:n]
    gaps = np.where(toks < vocab, gaps, np.inf)
    ctl = None
    if control is not None:
        c_logits = control(params, seq_d, p - 1, n=n_pad)
        ctl = np.asarray(jax.device_get(jax.jit(_control_gaps)(ref, c_logits)))[:n]
    return gaps, ctl


def check(fwd, params, requests, served: dict, seed: int, target: int,
          vocab: int, control=None) -> Check:
    picked = sample(requests, served, seed, target)
    worst, worst_ctl, tokens = 0.0, 0.0, 0
    for r in picked:
        toks = np.asarray(served[r.rid], np.int32)
        g, c = readings(fwd, params, r.prompt, toks, vocab, control)
        tokens += len(toks)
        if len(g):
            worst = max(worst, float(g.max()))
        if c is not None and len(c):
            worst_ctl = max(worst_ctl, float(c.max()))
    if not picked:
        worst = float("inf")
    return Check(worst, len(picked), tokens,
                 worst_ctl if control is not None else None)
