"""Attention functionals.

`scaled_dot_product_attention` is the single entry point (ref gap: the snapshot's only
fused attention is `operators/fused/fused_attention_op.cu`, single-device).  The dense
path is a jnp composition; the flash path is a Pallas TPU kernel
(paddle_tpu/ops/flash_attention.py) selected automatically for long sequences on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework import random as _random
from ...tensor.tensor import Tensor, apply_op, _unwrap


def _dense_sdpa(q, k, v, mask, dropout_p, is_causal, scale, training=True):
    # q,k,v: [B, S, H, D] (paddle layout)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qT = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * s
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p and training:
        from .common import _dropout_mask_mul

        # key-residual dropout (mask regenerated in bwd — see common.py):
        # the [B,H,S,S] probs mask is the single largest dropout residual
        probs = _dropout_mask_mul(probs, _random.get_rng_key(),
                                  float(dropout_p), True, tuple(probs.shape))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vT)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None, backend="auto", name=None):
    """query/key/value: [batch, seq, num_heads, head_dim] (paddle layout)."""

    from ...core.device import is_tpu_backend
    from ...distributed.sharding_ctx import (local_shape, shard_index,
                                             shard_kernel)

    qv = _unwrap(query)
    kv = _unwrap(key)
    bshd = "b-h-"  # [batch, seq, heads, head_dim]: see sharding_ctx.shard_kernel

    # fused short-sequence path (encoder workloads: BERT/ERNIE S<=512): one
    # Pallas kernel per step with probs + dropout masks held in VMEM — the
    # dense path's [B,H,S,S] logits/probs/mask HBM round-trips disappear
    # (ops/encoder_attention.py; ref fused_attention_op.cu regime)
    if backend == "auto" and attn_mask is None and qv.ndim == 4 \
            and is_tpu_backend():
        from ...ops import encoder_attention as _enc

        b, s, h, d = local_shape(qv.shape, bshd)
        if _enc.supported(b * h, s, d, kv.shape[1]):
            rate = float(dropout_p) if (dropout_p and training) else 0.0

            def _f(q, k, v):
                # drawn OUTSIDE the shard_map: the RNG stream is global state
                seed = jax.random.bits(
                    _random.get_rng_key(), (2,), jnp.uint32
                ).astype(jnp.int32) if rate > 0.0 else jnp.zeros((2,), jnp.int32)
                return shard_kernel(
                    lambda q, k, v, seed: _enc.encoder_attention(
                        q, k, v, seed=seed + shard_index(), scale=scale,
                        dropout_rate=rate, causal=is_causal),
                    (bshd, bshd, bshd, ""), bshd)(q, k, v, seed)

            return apply_op(_f, (query, key, value), name="encoder_attention")

    use_flash = False
    if backend in ("auto", "flash"):
        seq = qv.shape[1]
        seq_k = kv.shape[1]
        hd = qv.shape[-1]
        no_drop = dropout_p == 0.0 or not training
        if backend == "flash" and not no_drop:
            import warnings

            warnings.warn(
                "backend='flash' with active attention dropout falls back to the "
                "dense SDPA path (the Pallas flash kernel has no dropout); full "
                "[B,H,S,S] attention probs will be materialized")
        from ...ops.flash_attention import supports_seq

        blocks_ok = supports_seq(seq) and supports_seq(seq_k)
        causal_ok = not is_causal or seq <= seq_k
        # blocks_ok gates BOTH paths: an explicit backend='flash' request
        # with an untileable length falls back to dense instead of raising
        # deep inside _auto_block
        use_flash = (backend == "flash" and no_drop and causal_ok
                     and blocks_ok) or (
            is_tpu_backend() and seq >= 1024 and blocks_ok and causal_ok
            and hd in (64, 128, 256) and attn_mask is None and no_drop
        )

    if use_flash:
        from ...ops.flash_attention import flash_attention as _flash

        _f = shard_kernel(
            lambda q, k, v: _flash(q, k, v, causal=is_causal, scale=scale),
            (bshd, bshd, bshd), bshd)
        return apply_op(_f, (query, key, value), name="flash_attention")

    def _f(q, k, v, m):
        return _dense_sdpa(q, k, v, m, dropout_p, is_causal, scale, training)

    return apply_op(_f, (query, key, value, attn_mask), name="sdpa")


# paddle.nn.functional.flash_attention module-style API parity
def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, training=training)
    if return_softmax:
        return out, None
    return out, None
