"""Lightning (linear) attention: the recurrent state a head and its two passes.

A head keeps the matrix S [N, P] (key width by value width) of

    S_t = lambda_h S_{t-1} + k_t^T v_t          o_t = q_t S_t

with a constant decay lambda_h a head.  That is the Mamba-2 state update of
ops/ssm_update.py with the decay constant, B = k, C = q, x dt = v and one
"group" a head, so both passes are that file's, under this layer's names:

``lightning_update``      one token a serving slot (the decode tick): the
    state pass is `ssm_update.state_pass` — the same Pallas kernel, N on the
    sublanes and the value width on the lanes ([heads, 128, 128] float32 a
    slot as published: one head fills the 128 lanes the Mamba-2 layout fills
    with two), named ``lightning_update`` so its device events carry the name
``lightning_chunk_scan``  T tokens from a state (a prefill chunk) through
    `ssm_update.ssd_chunk_scan`, the chunked form in plain jnp

A slot's state is float32 [heads, N, P]: S[h][n, p] = state[h, n, p].  A row
that is not real (an idle slot, a chunk's padded tail) leaves it exactly as
it was: its decay is 1 and its v is 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ssm_update as _ssm


def log_decay(heads):
    """log lambda_h = -2^(-8 h / heads), h = 1 .. heads (the slopes of
    Lightning Attention-2 / MiniMax-01), float32 [heads]."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / heads)


def lightning_update(state, q, k, v, log_lambda, valid, use_kernel=None,
                     interpret=None):
    """Advance every slot one token.  state float32 [B, H, N, P]; q, k
    [B, H, N]; v [B, H, P]; log_lambda [H]; valid bool [B].  Returns (o
    float32 [B, H, P] = q S_t, new state)."""
    B, H, N, P = state.shape
    with jax.named_scope("lightning_update"):
        live = valid[:, None, None]
        da = jnp.where(live, jnp.exp(log_lambda.astype(jnp.float32))[None, :, None],
                       1.0)
        da = jnp.broadcast_to(da, (B, H, P))
        xdt = jnp.where(live, v.astype(jnp.float32), 0.0)
        s_new, o = _ssm.state_pass(
            state, xdt, da, k.astype(jnp.float32), q.astype(jnp.float32),
            name="lightning_update", use_kernel=use_kernel, interpret=interpret)
    return o, s_new


def lightning_chunk(state, q, k, v, log_lambda, n_valid, chunk_size=128):
    """Advance rows by up to T tokens each.  state [B, H, N, P]; q, k
    [B, T, H, N]; v [B, T, H, P]; n_valid int32 [B] (the leading tokens that
    are real).  Returns (o float32 [B, T, H, P], new state)."""
    B, T, H, _ = q.shape
    with jax.named_scope("lightning_chunk_scan"):
        real = (jnp.arange(T)[None, :] < n_valid[:, None]).astype(jnp.float32)
        o, final = _ssm.ssd_chunk_scan(
            v, jnp.broadcast_to(real[..., None], (B, T, H)),
            log_lambda.astype(jnp.float32), k, q,
            _ssm.rows_to_heads(state, H), chunk_size)
        return o, _ssm.heads_to_rows(final, H).astype(state.dtype)
