"""Pallas TPU fused 1x1-conv + BatchNorm kernel family (ResNet fast path).

Reference analog: the conv+BN fusion the reference applies at inference
(`/root/reference/paddle/fluid/framework/ir/conv_bn_fuse_pass.cc`) and the
cuDNN-style fused BN-stats/apply epilogues its CUDA kernels rely on
(`/root/reference/paddle/phi/kernels/gpu/batch_norm_kernel.cu` saved-stats
contract).  This is the TRAINING-mode analog, designed for the TPU memory
system rather than translated.

The ResNet-50 train step is HBM-bound by its schedule (about 44.8 GB moved
per step at batch 128 against ~17.5 ms of conv MXU time; the timed breakdown
that said so was deleted with its records in PR 21 and has not been retaken
on the attached chip).  Every win here is a removed full-tensor memory pass:

- forward "fold": the PREVIOUS BatchNorm's normalize + ReLU is applied on the
  fly to the conv input as it streams from HBM, so the normalized activation
  is never materialized (XLA cannot fuse producers into convolution inputs).
  The conv output's per-channel sum/sumsq accumulate in the same kernel's
  epilogue.  The un-folded forward stays on XLA: its conv+stats fusion is
  already minimal-traffic there.
- backward: ONE kernel computes dy_tot (the sum/sumsq backward terms), dX,
  dW, and the fold backward (ReLU mask, per-channel dscale/doffset reduces)
  sharing a single HBM read of each operand.  XLA autodiff emits separate
  dW / dX convolution fusions that EACH re-read dy and y (the profiled
  ~1.3-1.4 ms multiply_reduce fusions).

Layout contract: NHWC with W padded to a multiple of 8 ("W'") so every
[1, bh, W', C] block reshapes to 2-D MXU rows free of sublane re-tiling; pad
columns (w >= wv) hold zeros, enforced by in-kernel masks wherever an affine
offset could make them non-zero.  dy_tot is formed in bf16 (the stats terms
are per-channel and small relative to dy; measured −12% kernel time vs f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._prng import interpret_default as _interpret_default


def _params(interpret, n=2):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * n)


def _pick_bh(H, Wp, per_row_bytes, budget=3 * 1024 * 1024):
    """Largest divisor of H whose block stays under ~budget bytes/step,
    leaving VMEM room for Pallas double-buffering of the streamed blocks."""
    best = 1
    for bh in range(1, H + 1):
        if H % bh == 0 and bh * Wp * per_row_bytes <= budget:
            best = bh
    return best


def _row_mask(M, Wp, Wv):
    w_id = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) % Wp
    return (w_id < Wv).astype(jnp.float32)


# ---------------------------------------------------------------- forward

def _fwd_kernel(x_ref, w_ref, s_ref, o_ref, y_ref, st_ref,
                *, relu, K, Wp, Wv):
    j = pl.program_id(1)
    _, bh = x_ref.shape[0], x_ref.shape[1]
    Cout = y_ref.shape[-1]
    M = bh * Wp
    x2 = x_ref[...].reshape(M, K)
    a = x2.astype(jnp.float32) * s_ref[...].reshape(K) + o_ref[...].reshape(K)
    if relu:
        a = jnp.maximum(a, 0.0)
    if Wp != Wv:
        a = a * _row_mask(M, Wp, Wv)
    x2 = a.astype(x2.dtype)
    acc = jax.lax.dot_general(x2, w_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    y = acc.astype(y_ref.dtype)
    y_ref[...] = y.reshape(y_ref.shape)
    # stats on the ROUNDED output (what downstream consumers read), matching
    # the composed batch_norm path which reduces the materialized bf16 y
    yf = y.astype(jnp.float32)
    st = jnp.stack([jnp.broadcast_to(jnp.sum(yf, 0)[None, :], (8, Cout)),
                    jnp.broadcast_to(jnp.sum(yf * yf, 0)[None, :], (8, Cout))],
                   0)[None]

    @pl.when(j == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    st_ref[...] += st


def _fwd_fold(x, w, scale, offset, relu, Wv, interpret):
    N, H, Wp, K = x.shape
    Cout = w.shape[-1]
    bh = _pick_bh(H, Wp, (K + Cout) * 2 + Cout * 4)
    gi, gj = N, H // bh
    kern = functools.partial(_fwd_kernel, relu=relu, K=K, Wp=Wp, Wv=Wv)
    y, stp = pl.pallas_call(
        kern,
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec((1, bh, Wp, K), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((K, Cout), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, Wp, Cout), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 2, 8, Cout), lambda i, j: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, Wp, Cout), x.dtype),
            jax.ShapeDtypeStruct((N, 2, 8, Cout), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(interpret),
    )(x, w.reshape(K, Cout), scale, offset)
    s = jnp.sum(stp[:, :, 0, :], axis=0)
    return y, s[0], s[1]


def _fwd_plain(x, w):
    """No-fold forward: XLA's conv + fused sum/sumsq epilogue is already
    minimal-traffic; only the backward needs the combined kernel."""
    K, Cout = w.shape[2], w.shape[3]
    y = jax.lax.dot_general(x, w.reshape(K, Cout), (((3,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32).astype(x.dtype)
    yf = y.astype(jnp.float32)
    return y, jnp.sum(yf, (0, 1, 2)), jnp.sum(yf * yf, (0, 1, 2))


# ---------------------------------------------------------------- backward

def _bwd_kernel(dy_ref, y_ref, x_ref, wt_ref, s_ref, o_ref, ds_ref,
                dx_ref, dw_ref, dso_ref, *, fold, relu, K, Wp, Wv):
    i = pl.program_id(0)
    j = pl.program_id(1)
    _, bh = dy_ref.shape[0], dy_ref.shape[1]
    Cout = dy_ref.shape[-1]
    M = bh * Wp
    dy2 = dy_ref[...].reshape(M, Cout)
    y2 = y_ref[...].reshape(M, Cout)
    mask = _row_mask(M, Wp, Wv) if Wp != Wv else None
    # bf16 dy_tot: ds1/ds2 are per-channel and small next to dy
    dyt = dy2 + (ds_ref[0, :].astype(dy2.dtype)
                 + y2 * (2.0 * ds_ref[1, :]).astype(dy2.dtype))
    if mask is not None:
        dyt = dyt * mask.astype(dyt.dtype)
    x2 = x_ref[...].reshape(M, K)
    if fold:
        a = x2.astype(jnp.float32) * s_ref[...].reshape(K) + o_ref[...].reshape(K)
        xf = jnp.maximum(a, 0.0) if relu else a
        if mask is not None:
            xf = xf * mask
        xf = xf.astype(x2.dtype)
    else:
        xf = x2
    dw = jax.lax.dot_general(xf, dyt, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    first = jnp.logical_and(i == 0, j == 0)

    @pl.when(first)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dso_ref[...] = jnp.zeros_like(dso_ref)

    dw_ref[...] += dw
    dxf = jax.lax.dot_general(dyt, wt_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if fold:
        g = jnp.where(a > 0.0, dxf, 0.0) if relu else dxf
        dx_ref[...] = (g * s_ref[...].reshape(K)).astype(dx_ref.dtype).reshape(dx_ref.shape)
        dsc = jnp.sum(g * x2.astype(jnp.float32), axis=0)
        dof = jnp.sum(g, axis=0)
        dso_ref[...] += jnp.stack([jnp.broadcast_to(dsc[None, :], (8, K)),
                                   jnp.broadcast_to(dof[None, :], (8, K))], 0)
    else:
        dx_ref[...] = dxf.astype(dx_ref.dtype).reshape(dx_ref.shape)


def _bwd_call(dy, y, x, w, scale, offset, ds1, ds2, relu, Wv, interpret):
    N, H, Wp, K = x.shape
    Cout = w.shape[-1]
    fold = scale is not None
    if not fold:
        scale = jnp.zeros((1, K), jnp.float32)
        offset = jnp.zeros((1, K), jnp.float32)
    wt = w.reshape(K, Cout).T
    ds = jnp.concatenate([ds1.reshape(1, Cout).astype(jnp.float32),
                          ds2.reshape(1, Cout).astype(jnp.float32)], 0)
    bh = _pick_bh(H, Wp, (2 * Cout + 2 * K) * 2 + (Cout + K) * 2)
    gi, gj = N, H // bh
    kern = functools.partial(_bwd_kernel, fold=fold, relu=relu, K=K, Wp=Wp, Wv=Wv)
    dx, dwp, dsop = pl.pallas_call(
        kern,
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec((1, bh, Wp, Cout), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bh, Wp, Cout), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bh, Wp, K), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((Cout, K), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
            pl.BlockSpec((1, K), lambda i, j: (0, 0)),
            pl.BlockSpec((2, Cout), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, Wp, K), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((K, Cout), lambda i, j: (0, 0)),
            pl.BlockSpec((2, 8, K), lambda i, j: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, Wp, K), x.dtype),
            jax.ShapeDtypeStruct((K, Cout), jnp.float32),
            jax.ShapeDtypeStruct((2, 8, K), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(interpret),
    )(dy, y, x, wt, scale, offset, ds)
    dw = dwp.reshape(1, 1, K, Cout)
    if fold:
        return dx, dw, dsop[0, :1, :], dsop[1, :1, :]
    return dx, dw, None, None


# ---------------------------------------------------------------- custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv1x1_bn(x, w, scale, offset, relu, Wv):
    return _conv1x1_bn_fwd(x, w, scale, offset, relu, Wv)[0]


def _conv1x1_bn_fwd(x, w, scale, offset, relu, Wv):
    if scale is None:
        y, s1, s2 = _fwd_plain(x, w)
    else:
        y, s1, s2 = _fwd_fold(x, w, scale, offset, relu, Wv,
                              _interpret_default())
    return (y, s1, s2), (x, w, scale, offset, y)


def _conv1x1_bn_bwd(relu, Wv, res, cts):
    x, w, scale, offset, y = res
    dy, ds1, ds2 = cts
    dx, dw, dsc, dof = _bwd_call(dy, y, x, w, scale, offset, ds1, ds2, relu,
                                 Wv, _interpret_default())
    return dx, dw.astype(w.dtype), dsc, dof


_conv1x1_bn.defvjp(_conv1x1_bn_fwd, _conv1x1_bn_bwd)


def supported(x_shape, w_shape):
    """Fast-path admission: 4-D NHWC, 1x1 kernel, lane-aligned channels,
    W a multiple of 8 (the caller pads)."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    N, H, Wp, K = x_shape
    kh, kw, K2, Cout = w_shape
    return (kh == 1 and kw == 1 and K2 == K and Wp % 8 == 0
            and K % 64 == 0 and Cout % 64 == 0 and N >= 1)


def conv1x1_bn(x, w, scale=None, offset=None, relu=True, wv=None):
    """y = conv1x1(act(x*scale+offset)), plus per-channel (sum, sumsq) of y.

    x: [N, H, W', Cin] (W' % 8 == 0; columns >= wv hold zeros).  w: [1, 1,
    Cin, Cout].  scale/offset: f32 [1, Cin] fold of the previous BatchNorm
    (None = input already normalized; no fold, XLA forward).  Returns
    (y, s1, s2); s1/s2 are f32 [Cout] sums over valid columns.  The backward
    runs the combined Pallas kernel in all cases.
    """
    wv = wv or x.shape[2]
    if not supported(x.shape, w.shape):
        raise ValueError(f"conv1x1_bn: unsupported shapes {x.shape} {w.shape}")
    return _conv1x1_bn(x, w, scale, offset, bool(relu), int(wv))
