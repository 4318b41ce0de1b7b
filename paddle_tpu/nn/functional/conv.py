"""Convolutions (ref: python/paddle/nn/functional/conv.py, phi ConvKernel/cudnn).

On TPU these lower to XLA `convolution` ops that tile directly onto the MXU — the
entire cudnn algo-selection/workspace machinery of the reference
(paddle/phi/kernels/gpudnn/conv_kernel.cu) collapses into XLA's conv emitter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...tensor.tensor import apply_op


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(i) for i in v)
    return (int(v),) * n


def _conv_padding(padding, nd):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nd
    padding = list(padding)
    if len(padding) == nd:
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * nd:
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(nd)]
    # paddle also allows [[0,0],[0,0],[h0,h1],[w0,w1]]
    if len(padding) == nd + 2 and isinstance(padding[0], (list, tuple)):
        return [tuple(p) for p in padding[2:]]
    raise ValueError(f"bad padding {padding}")


# When True, channel-first convs are internally rewritten to channel-last
# ("NHWC"/"HWIO") with boundary transposes; when False the NCHW dimension numbers
# are handed to XLA directly (its layout assignment picks physical layouts anyway).
# Direct NCHW is the default: XLA's layout assignment already picks physical
# layouts (an earlier A/B on v5e read 2245 vs 2198 img/s on ResNet-50 train;
# its record was deleted in PR 21 and it has not been retaken).
_INTERNAL_CHANNEL_LAST = False


def _conv(x, weight, bias, stride, padding, dilation, groups, data_format, nd, name):
    strides = _pair(stride, nd)
    dilations = _pair(dilation, nd)
    pad = _conv_padding(padding, nd)
    spatial = "DHW"[3 - nd:]
    channel_first = data_format in ("NCHW", "NCL", "NCDHW")
    relayout = channel_first and _INTERNAL_CHANNEL_LAST
    if channel_first and not relayout:
        lhs_spec = "NC" + spatial
        rhs_spec = "OI" + spatial
    else:
        lhs_spec = "N" + spatial + "C"
        rhs_spec = spatial + "IO" if relayout else "OI" + spatial
    dn = (lhs_spec, rhs_spec, lhs_spec)

    def _f(v, w, b):
        # NB: no preferred_element_type here — the MXU accumulates bf16 in f32
        # internally, and an explicit f32 accumulate breaks the conv transpose rule
        # under AD (f32 cotangent vs bf16 weight).  lax.conv requires equal input
        # dtypes; follow the activation dtype when a layer wasn't cast.
        if w.dtype != v.dtype:
            w = w.astype(v.dtype)
        if relayout:
            v = jnp.moveaxis(v, 1, -1)  # NC... -> N...C
            w = jnp.transpose(w, tuple(range(2, 2 + nd)) + (1, 0))  # OI... -> ...IO
        out = jax.lax.conv_general_dilated(
            v, w, window_strides=strides, padding=pad,
            rhs_dilation=dilations, dimension_numbers=dn,
            feature_group_count=groups,
        )
        if b is not None:
            shape = [1] * out.ndim
            shape[lhs_spec.index("C")] = b.shape[0]
            if relayout:
                shape = [1] * (out.ndim - 1) + [b.shape[0]]
            out = out + b.reshape(shape)
        if relayout:
            out = jnp.moveaxis(out, -1, 1)
        return out

    return apply_op(_f, (x, weight, bias), name=name)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, data_format, 1, "conv1d")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, data_format, 2, "conv2d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW", name=None):
    return _conv(x, weight, bias, stride, padding, dilation, groups, data_format, 3, "conv3d")


def _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation, groups,
                    data_format, nd, output_size, name):
    strides = _pair(stride, nd)
    dilations = _pair(dilation, nd)
    opad = _pair(output_padding, nd)
    if isinstance(padding, str):
        raise ValueError("string padding unsupported for conv_transpose")
    pad = _conv_padding(padding, nd)

    if data_format in ("NCHW", "NCL", "NCDHW"):
        lhs_spec = "NC" + "DHW"[3 - nd:]
    else:
        lhs_spec = "N" + "DHW"[3 - nd:] + "C"
    rhs_spec = "IO" + "DHW"[3 - nd:]  # paddle weight layout: [in, out/groups, *k]
    dn = (lhs_spec, rhs_spec, lhs_spec)

    def _f(v, w, b):
        # transpose conv = gradient of conv: use conv_transpose with IO layout
        k = w.shape[2:]
        tpad = [
            (d * (kk - 1) - p[0], d * (kk - 1) - p[1] + op)
            for kk, d, p, op in zip(k, dilations, pad, opad)
        ]
        if groups > 1:
            # split groups manually (lax.conv_transpose lacks feature groups)
            cin = v.shape[lhs_spec.index("C")]
            gs = cin // groups
            outs = []
            for g in range(groups):
                sl = [slice(None)] * v.ndim
                sl[lhs_spec.index("C")] = slice(g * gs, (g + 1) * gs)
                wg = w[g * gs:(g + 1) * gs]
                outs.append(
                    jax.lax.conv_transpose(
                        v[tuple(sl)], wg, strides=strides, padding=tpad,
                        rhs_dilation=dilations, dimension_numbers=dn, transpose_kernel=False,
                    )
                )
            out = jnp.concatenate(outs, axis=lhs_spec.index("C"))
        else:
            out = jax.lax.conv_transpose(
                v, w, strides=strides, padding=tpad,
                rhs_dilation=dilations, dimension_numbers=dn, transpose_kernel=False,
            )
        if b is not None:
            shape = [1] * out.ndim
            shape[lhs_spec.index("C")] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    return apply_op(_f, (x, weight, bias), name=name)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None, data_format="NCL", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation,
                           groups, data_format, 1, output_size, "conv1d_transpose")


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None, data_format="NCHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation,
                           groups, data_format, 2, output_size, "conv2d_transpose")


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None, data_format="NCDHW", name=None):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation,
                           groups, data_format, 3, output_size, "conv3d_transpose")
