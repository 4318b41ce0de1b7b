"""Pallas TPU fused short-sequence attention (forward + backward, dropout).

Reference analog: `/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu`
(+ fmha_ref.h) — the reference's only fused attention is exactly this regime:
full [S, S] probs held on-chip for modest S, no online-softmax tiling.  The
flash kernel (ops/flash_attention.py) covers long sequences; at S ~ 128-512 its
per-(b,h) grid makes tiny DMA blocks and loses to dense XLA (measured 25 ms vs
6.7 ms per ERNIE layer fwd+bwd).  This kernel instead packs G heads per grid
step — large DMA blocks — and computes each head's whole attention in VMEM:

    s = q @ k^T * scale        [S, S] f32, softmax rows
    p = dropout(softmax(s))    mask from the ON-CORE PRNG (pltpu), no HBM bits
    o = p @ v

The backward regenerates the dropout mask from the same per-(step, head) seed
and recomputes s/p in VMEM (flash-style recompute, no probs residual), so the
only saved tensors are the natural q/k/v inputs.

Dense-path cost this replaces (ERNIE b512 s128 h12 d64): [B,H,S,S] logits+probs
round-trips plus u16 mask traffic — ~9.9 ms/layer fwd+bwd with dropout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from ._prng import (interpret_default as _interpret_default,
                    keep_mask as _keep_mask,
                    parallel_params as _params)


# VMEM budget: per head S*S f32 probs (+ masks) plus G*(q,k,v,o) blocks.
_VMEM_ELEMS = 2 * 1024 * 1024


def pick_g(bh, s, d):
    """Heads per grid step.  g=16 measured fastest for fwd+bwd at the encoder
    shapes (5.47 ms/layer vs 5.66 at g=8, 6.61 at g=4; BH=6144/S=128/D=64
    with dropout); fall through to any divisor that fits VMEM."""
    for g in (16, 8, 4, 2, 1):
        if bh % g == 0 and g * s * d * 4 + g * s * s <= _VMEM_ELEMS:
            return g
    return None


def supported(bh, s, d, seq_kv=None):
    if seq_kv is not None and seq_kv != s:
        return False  # self-attention only (q/k same length)
    return (s % 128 == 0 and s <= 512 and d in (64, 128)
            and pick_g(bh, s, d) is not None)


def _block_masks(seed_ref, pid, g, s, rate, interpret):
    """[G, S, S] keep-masks for this grid step (fwd and bwd call with the same
    (seed, pid) so the masks regenerate bit-identically — shared seed-mix
    contract in ops/_prng.py)."""
    return _keep_mask(seed_ref, pid, (g, s, s), rate, interpret)


def _softmax_rows(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _causal_neg(s_len):
    qpos = jax.lax.broadcasted_iota(jnp.int32, (s_len, s_len), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (s_len, s_len), 1)
    return jnp.where(qpos >= kpos, 0.0, -1e30)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, *, scale, rate, g,
                causal, interpret):
    # one BATCHED dot_general over the G heads per MXU dispatch: measured ~2x
    # the throughput of a python loop of per-head 2D matmuls at these shapes
    pid = pl.program_id(0)
    s_len = q_ref.shape[1]
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = s + _causal_neg(s_len)[None]
    p = _softmax_rows(s)
    if rate > 0.0:
        keep = _block_masks(seed_ref, pid, g, s_len, rate, interpret)
        p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    o_ref[...] = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((2,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32
                                     ).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, scale, rate, g, causal, interpret):
    pid = pl.program_id(0)
    s_len = q_ref.shape[1]
    inv = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = s + _causal_neg(s_len)[None]
    p = _softmax_rows(s)
    if rate > 0.0:
        keep = _block_masks(seed_ref, pid, g, s_len, rate, interpret)
        p_d = jnp.where(keep, p * inv, 0.0)
    else:
        p_d = p
    # o = p_d @ v   (batch dim 0 = heads throughout)
    dv_ref[...] = jax.lax.dot_general(
        p_d.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp_d = jax.lax.dot_general(do, v.astype(do.dtype),
                               (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)
    dp = jnp.where(keep, dp_d * inv, 0.0) if rate > 0.0 else dp_d
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True)) * scale
    dq_ref[...] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[...] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attn_core(q, k, v, seed, scale, rate, causal):
    out, _ = _attn_fwd(q, k, v, seed, scale, rate, causal)
    return out


def _attn_fwd(q, k, v, seed, scale, rate, causal):
    bh, s, d = q.shape
    g = pick_g(bh, s, d)
    interpret = _interpret_default()
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, rate=rate, g=g,
                          causal=causal, interpret=interpret),
        grid=(bh // g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        compiler_params=_params(interpret),
        name="encoder_attention_fwd",
    )(seed, q, k, v)
    return out, (q, k, v, seed)


def _attn_bwd(scale, rate, causal, res, do):
    q, k, v, seed = res
    bh, s, d = q.shape
    g = pick_g(bh, s, d)
    interpret = _interpret_default()
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, rate=rate, g=g,
                          causal=causal, interpret=interpret),
        grid=(bh // g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, s, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
        compiler_params=_params(interpret),
        name="encoder_attention_bwd",
    )(seed, q, k, v, do)
    return dq, dk, dv, None


_attn_core.defvjp(
    lambda q, k, v, seed, scale, rate, causal: _attn_fwd(q, k, v, seed, scale,
                                                         rate, causal),
    _attn_bwd)


def encoder_attention(q, k, v, seed=None, scale=None, dropout_rate=0.0,
                      causal=False):
    """Fused self-attention for short sequences.

    q/k/v: [B, S, H, D] (paddle layout); seed: int32 [2] array (required when
    dropout_rate > 0); returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if dropout_rate > 0.0 and seed is None:
        raise ValueError("encoder_attention: dropout_rate > 0 requires a seed")
    if seed is None:
        seed = jnp.zeros((2,), jnp.int32)
    if not supported(b * h, s, d, k.shape[1]):
        raise ValueError(
            f"encoder_attention: shape B*H={b*h} S={s} D={d} unsupported "
            "(need S%128==0, S<=512, D in (64,128)) — use the dense SDPA path")

    def pack(t):
        return jnp.swapaxes(t, 1, 2).reshape(b * h, s, d)

    out = _attn_core(pack(q), pack(k), pack(v), seed, float(scale),
                     float(dropout_rate), bool(causal))
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
