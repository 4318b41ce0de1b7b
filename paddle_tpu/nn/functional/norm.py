"""Normalization functionals (ref: python/paddle/nn/functional/norm.py, phi BatchNormKernel).

Running-stat updates are returned functionally and written back to layer buffers by the
calling Layer — keeping the computation pure so whole steps jit cleanly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...tensor.tensor import Tensor, apply_op, _unwrap


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False,
               momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None, name=None):
    ch_axis = 1 if data_format.startswith("NC") else -1
    use_batch_stats = training and not use_global_stats

    if use_batch_stats:
        # batch stats computed ONCE, in f32 (bf16 mean/var loses precision),
        # shared by the normalization, the backward, and the running-stat
        # update — the reference kernel's saved_mean/saved_variance contract
        # (phi BatchNormKernel).  sum/sum-of-squares form: ONE fused
        # multi-output reduce over the activation instead of mean + var
        # (jnp.var re-reads the input to subtract the mean) — measured
        # +7.7% on the ResNet-50 train step (51.1 -> 47.5 ms, v5e b128);
        # f32 accumulation keeps E[x^2]-E[x]^2 BN-safe, clamped at 0
        def _stats(v):
            ch = ch_axis % v.ndim
            axes = tuple(i for i in range(v.ndim) if i != ch)
            vf = v.astype(jnp.float32)
            s1 = jnp.sum(vf, axis=axes)
            s2 = jnp.sum(vf * vf, axis=axes)
            n = 1
            for i in axes:
                n *= v.shape[i]
            m = s1 / n
            return m, jnp.maximum(s2 / n - m * m, 0.0)

        mean_t, var_t = apply_op(_stats, (x,), name="batch_norm_stats")
    else:
        mean_t, var_t = running_mean, running_var

    def _f(v, m, s, w, b):
        # collapse to a per-channel affine in f32, then one fused
        # multiply-add over the activation in its own dtype
        scale = jax.lax.rsqrt(s.astype(jnp.float32) + epsilon)
        if w is not None:
            scale = scale * w.astype(jnp.float32)
        offset = -m.astype(jnp.float32) * scale
        if b is not None:
            offset = offset + b.astype(jnp.float32)
        shape = [1] * v.ndim
        shape[ch_axis] = v.shape[ch_axis]
        return v * scale.reshape(shape).astype(v.dtype) \
            + offset.reshape(shape).astype(v.dtype)

    out = apply_op(_f, (x, mean_t, var_t, weight, bias), name="batch_norm")

    if use_batch_stats and isinstance(running_mean, Tensor):
        # functional stat update written back to the buffers (ref
        # BatchNormKernel saved stats).  Routed through apply_op so a static
        # Program capture records it — set_value then promotes the write to
        # live program state (MeanOut/VarianceOut analog) instead of baking
        # the build-time placeholder stats.
        v = _unwrap(x)
        ch = ch_axis % v.ndim
        n = 1
        for i in range(v.ndim):
            if i != ch:
                n *= v.shape[i]
        factor = n / max(n - 1, 1)
        new_mean = apply_op(
            lambda rm, m: momentum * rm + (1 - momentum) * m,
            (running_mean, mean_t.detach()), name="bn_moving_mean")
        new_var = apply_op(
            lambda rv, s: momentum * rv + (1 - momentum) * (s * factor),
            (running_var, var_t.detach()), name="bn_moving_var")
        running_mean.set_value(new_mean)
        running_var.set_value(new_var)
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    ns = normalized_shape if isinstance(normalized_shape, (list, tuple)) else [normalized_shape]
    nd = len(ns)

    def _f(v, w, b):
        axes = tuple(range(v.ndim - nd, v.ndim))
        # SHIFTED sum/sum-of-squares stats in ONE fused f32 multi-output
        # reduce (jnp.var re-reads the input to subtract the mean — same
        # single-pass rewrite that bought +7.7% on BN above).  The shift by
        # the row's first element keeps the summands at the scale of the
        # SPREAD, not the mean, so E[d^2]-E[d]^2 cannot cancel
        # catastrophically when |mean| >> std.  f32 stats regardless of
        # activation dtype (bf16 mean/var at h>=768 degrades normalization).
        vf = v.astype(jnp.float32)
        n = 1
        for i in axes:
            n *= v.shape[i]
        first = jax.lax.slice_in_dim(vf, 0, 1, axis=axes[0])
        for ax in axes[1:]:
            first = jax.lax.slice_in_dim(first, 0, 1, axis=ax)
        d = vf - first
        s1 = jnp.sum(d, axis=axes, keepdims=True)
        s2 = jnp.sum(d * d, axis=axes, keepdims=True)
        dmean = s1 / n
        var = jnp.maximum(s2 / n - dmean * dmean, 0.0)
        mean = first + dmean
        out = ((vf - mean) * jax.lax.rsqrt(var + epsilon)).astype(v.dtype)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        return out

    return apply_op(_f, (x, weight, bias), name="layer_norm")


def fused_dropout_add_layer_norm(x, residual, weight, bias, p=0.0, epsilon=1e-5,
                                 training=True, name=None):
    """out = LayerNorm(residual + dropout(x)) — the transformer-encoder glue
    pattern, fused.  Ref: fluid/operators/fused/fused_dropout_helper.h
    (ResidualDropoutBias + LayerNorm epilogue of fused_attention /
    fused_feedforward).  On TPU this lowers to ONE Pallas kernel with on-core
    RNG (paddle_tpu/ops/fused_ln.py); elsewhere it runs the same math as the
    composed ops (key-residual dropout + single-pass f32 LN stats)."""
    from ...framework import random as _random

    rate = float(p) if training else 0.0
    eps = float(epsilon)

    def _f(xb, res, w, b):
        from ...core.device import is_tpu_backend

        if is_tpu_backend() and w is not None and b is not None \
                and xb.ndim >= 2:
            from ...distributed.sharding_ctx import (local_shape, shard_index,
                                                     shard_kernel)
            from ...ops import fused_ln as _k

            rows = "b" + "-" * (xb.ndim - 1)  # leading dim is the batch
            # rows the kernel sees: per shard when a mesh is active
            n = math.prod(local_shape(xb.shape, rows)[:-1])
            if _k.supported(n, xb.shape[-1]):
                if rate > 0.0:
                    key = _random.get_rng_key()
                    seed = jax.random.bits(key, (2,), jnp.uint32).astype(jnp.int32)
                else:
                    # no dropout -> no RNG stream advance (keeps seed-for-seed
                    # parity with the composed/CPU path in eval mode)
                    seed = jnp.zeros((2,), jnp.int32)
                return shard_kernel(
                    lambda xb, res, w, b, seed:
                        _k.fused_dropout_add_layer_norm(
                            xb, res, w, b, seed + shard_index(), rate, eps),
                    (rows, rows, "", "", ""), rows)(xb, res, w, b, seed)
        # composed path: identical math, jax.random mask
        xv = xb
        if rate > 0.0:
            from .common import _dropout_mask_mul

            xv = _dropout_mask_mul(xv, _random.get_rng_key(), rate, True,
                                   tuple(xv.shape))
        s = res.astype(jnp.float32) + xv.astype(jnp.float32)
        mean = jnp.mean(s, axis=-1, keepdims=True)
        c = s - mean
        var = jnp.mean(c * c, axis=-1, keepdims=True)
        out = (c * jax.lax.rsqrt(var + eps)).astype(xb.dtype)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        return out

    return apply_op(_f, (x, residual, weight, bias), name="fused_dropout_add_ln")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    def _f(v, w, b):
        axes = tuple(range(2, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + eps)
        if w is not None:
            shape = [1, -1] + [1] * (v.ndim - 2)
            out = out * w.reshape(shape)
        if b is not None:
            shape = [1, -1] + [1] * (v.ndim - 2)
            out = out + b.reshape(shape)
        return out

    return apply_op(_f, (x, weight, bias), name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None, data_format="NCHW", name=None):
    def _f(v, w, b):
        n, c = v.shape[0], v.shape[1]
        rest = v.shape[2:]
        g = v.reshape(n, num_groups, c // num_groups, *rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(v.shape)
        shape = [1, c] + [1] * len(rest)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out

    return apply_op(_f, (x, weight, bias), name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def _f(v):
        sq = jnp.square(v)
        half = size // 2
        c = v.shape[1]
        padded = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (v.ndim - 2))
        acc = jnp.zeros_like(v)
        for i in range(size):
            acc = acc + padded[:, i:i + c]
        return v / jnp.power(k + alpha * acc, beta)

    return apply_op(_f, (x,), name="local_response_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """Net-new (LLaMA-family); ref gap: Paddle snapshot has no fused RMSNorm."""

    def _f(v, w):
        ms = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=-1, keepdims=True)
        out = (v.astype(jnp.float32) * jax.lax.rsqrt(ms + epsilon)).astype(v.dtype)
        if w is not None:
            out = out * w
        return out

    return apply_op(_f, (x, weight), name="rms_norm")
