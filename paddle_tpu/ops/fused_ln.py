"""Pallas TPU fused dropout + residual-add + LayerNorm (forward + backward).

Reference analog: `/root/reference/paddle/fluid/operators/fused/fused_dropout_helper.h`
(ResidualDropoutBias + LayerNorm fused epilogues used by fused_attention /
fused_feedforward) — the CUDA fusion that keeps transformer-encoder glue off the
memory bus.  TPU edition: one kernel reads the residual and the branch output,
draws the dropout mask from the ON-CORE PRNG (pltpu.prng_random_bits — no mask
HBM traffic, no stored mask residual), adds, normalizes with f32 single-pass
sum/sumsq stats, and writes the normalized output.

Residual policy: the ONLY saved activation is `s = residual + dropout(branch)`
(the same tensor XLA's composed LN keeps); the dropout mask is REGENERATED in
the backward from the per-block seed, so no [n, h] bool/bits residuals exist —
that storage OOMed the dense-head ERNIE step when rbg masks became
non-rematerializable for XLA (tools/ernie_breakdown.py history).

Both grids are embarrassingly parallel: dgamma/dbeta come out as per-block
partials reduced by XLA outside the kernel (a [nblocks, h] f32 array — KBs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from ._prng import (interpret_default as _interpret_default,
                    keep_mask as _keep_mask_bits,
                    parallel_params as _params)


def _pick_bn(n, h):
    """Largest row-block that divides n and keeps bn*h temporaries VMEM-friendly."""
    budget = 256 * 1024  # elements per f32 temp (~1M)
    for bn in (512, 256, 128, 64, 32, 16, 8):
        if n % bn == 0 and bn * h <= budget:
            return bn
    return None


def _stats(s, eps):
    # two-pass mean/var: s lives in VMEM here, so the second pass is free and
    # avoids the E[x^2]-E[x]^2 cancellation when |mean| >> spread
    mean = jnp.mean(s, axis=-1, keepdims=True)
    c = s - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return mean, rstd


def _fwd_kernel(seed_ref, x_ref, y_ref, g_ref, b_ref, o_ref, s_ref,
                *, rate, eps, upscale, interpret):
    pid = pl.program_id(0)
    xf = x_ref[...].astype(jnp.float32)
    yf = y_ref[...].astype(jnp.float32)
    if rate > 0.0:
        keep = _keep_mask_bits(seed_ref, pid, y_ref.shape, rate, interpret)
        scale = (1.0 / (1.0 - rate)) if upscale else 1.0
        yf = jnp.where(keep, yf * scale, 0.0)
    s = xf + yf
    s_ref[...] = s.astype(s_ref.dtype)
    # stats and normalization run on the ROUNDED s (what the backward will
    # re-read): for bf16 activations this keeps fwd and bwd consistent — the
    # same function of the same stored tensor — instead of a ~2^-8 bias
    # between f32-fwd stats and bf16-recomputed bwd stats
    sq = s_ref[...].astype(jnp.float32)
    mean, rstd = _stats(sq, eps)
    out = (sq - mean) * rstd * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def _bwd_kernel(seed_ref, s_ref, g_ref, dz_ref,
                dx_ref, dy_ref, dg_ref, db_ref, *, rate, eps, upscale, interpret):
    pid = pl.program_id(0)
    s = s_ref[...].astype(jnp.float32)
    mean, rstd = _stats(s, eps)
    xhat = (s - mean) * rstd

    dz = dz_ref[...].astype(jnp.float32)
    dxhat = dz * g_ref[...].astype(jnp.float32)
    a = jnp.mean(dxhat, axis=-1, keepdims=True)
    b = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    ds = rstd * (dxhat - a - xhat * b)
    dx_ref[...] = ds.astype(dx_ref.dtype)
    if rate > 0.0:
        keep = _keep_mask_bits(seed_ref, pid, s_ref.shape, rate, interpret)
        scale = (1.0 / (1.0 - rate)) if upscale else 1.0
        dy_ref[...] = jnp.where(keep, ds * scale, 0.0).astype(dy_ref.dtype)
    else:
        dy_ref[...] = ds.astype(dy_ref.dtype)
    # per-block partials, broadcast over the 8-sublane min tile (Pallas TPU
    # rejects 1-row output blocks inside a larger array); XLA reduces the
    # [nblocks, 8, h] partials outside the kernel
    h = s.shape[-1]
    dg_ref[...] = jnp.broadcast_to(jnp.sum(dz * xhat, axis=0, keepdims=True), (8, h))
    db_ref[...] = jnp.broadcast_to(jnp.sum(dz, axis=0, keepdims=True), (8, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_core(x, y, gamma, beta, seed, rate, eps, upscale):
    out, _ = _fused_fwd(x, y, gamma, beta, seed, rate, eps, upscale)
    return out


def _fused_fwd(x, y, gamma, beta, seed, rate, eps, upscale):
    n, h = x.shape
    bn = _pick_bn(n, h)
    interpret = _interpret_default()
    out, s = pl.pallas_call(
        functools.partial(_fwd_kernel, rate=rate, eps=eps, upscale=upscale,
                          interpret=interpret),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x.dtype),
            jax.ShapeDtypeStruct((n, h), x.dtype),
        ],
        interpret=interpret,
        compiler_params=_params(interpret),
        name="fused_ln_fwd",
    )(seed, x, y, gamma.reshape(1, h), beta.reshape(1, h))
    return out, (s, gamma, seed)


def _fused_bwd(rate, eps, upscale, res, dz):
    s, gamma, seed = res
    n, h = s.shape
    bn = _pick_bn(n, h)
    nb = n // bn
    interpret = _interpret_default()
    dx, dy, dgp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, rate=rate, eps=eps, upscale=upscale,
                          interpret=interpret),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((8, h), lambda i: (i, 0)),
            pl.BlockSpec((8, h), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), s.dtype),
            jax.ShapeDtypeStruct((n, h), s.dtype),
            jax.ShapeDtypeStruct((nb * 8, h), jnp.float32),
            jax.ShapeDtypeStruct((nb * 8, h), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(interpret),
        name="fused_ln_bwd",
    )(seed, s, gamma.reshape(1, h), dz)
    dg = jnp.sum(dgp.reshape(nb, 8, h)[:, 0], axis=0).astype(gamma.dtype)
    db = jnp.sum(dbp.reshape(nb, 8, h)[:, 0], axis=0).astype(gamma.dtype)
    return dx, dy, dg, db, None


_fused_core.defvjp(lambda x, y, g, b, s, rate, eps, up: _fused_fwd(x, y, g, b, s, rate, eps, up),
                   _fused_bwd)


def supported(n, h):
    """Can the kernel tile this shape?  (rows split into an even block grid,
    feature dim lane-aligned)."""
    return h % 128 == 0 and _pick_bn(n, h) is not None


def fused_dropout_add_layer_norm(branch, residual, gamma, beta, seed, rate=0.0,
                                 eps=1e-12, upscale=True):
    """out = LayerNorm(residual + dropout(branch)) over the last dim.

    Argument order matches nn.functional.fused_dropout_add_layer_norm: the
    FIRST tensor is the branch output that gets dropped, the SECOND is the
    residual stream kept intact.  branch/residual: [..., H] (flattened to rows
    internally); gamma/beta: [H]; seed: int32 [2] array (two words of the
    per-call dropout stream; ignored at rate=0).
    """
    shape = branch.shape
    h = shape[-1]
    n = 1
    for d in shape[:-1]:
        n *= d
    if not supported(n, h):
        raise ValueError(
            f"fused_dropout_add_layer_norm: shape rows={n} h={h} not tileable "
            "(h must be a multiple of 128 and rows divisible by a block size "
            "of 8..512) — check ops.fused_ln.supported(n, h) and fall back to "
            "the composed nn.functional path")
    if rate >= 1.0:
        raise ValueError("fused_dropout_add_layer_norm requires rate < 1 "
                         "(rate>=1 drops the whole branch; compute LN(residual) "
                         "directly instead)")
    # kernel-internal convention: x = residual (kept), y = branch (dropped)
    x2 = residual.reshape(n, h)
    y2 = branch.reshape(n, h)
    out = _fused_core(x2, y2, gamma, beta, seed, float(rate), float(eps),
                      bool(upscale))
    return out.reshape(shape)
