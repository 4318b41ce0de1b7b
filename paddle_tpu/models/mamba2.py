"""The Mamba-2 mixer of the hybrid decoders (Nemotron-H, Granite-4.0-H), at
sizes given explicitly (``Mamba2Sizes``) so each family maps its own
config's keys onto one implementation:

  ``[z, xBC, dt] = W_in u``; ``xBC <- silu(conv(xBC) + b)`` (causal,
  depthwise, ``conv_kernel`` taps) ``-> x, B, C``; ``dt <- softplus(dt +
  dt_bias)``; ``S_t = exp(dt A) S_{t-1} + dt x (x) B``; ``y = S C + D x``;
  ``y <- RMSNorm over groups of inner / groups of (y silu(z))``; ``W_out y``

No projection bias; a convolution bias; ``dt_bias``, ``A_log`` and ``D`` in
float32 whatever the weights' dtype.  The state kernels are
ops/ssm_update.py's.  Parameters are created in the configured dtype, leaf
by leaf: nothing is built in float32 and cast.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework import random as _random
from ..ops import ssm_update as _ssm


class _Drawn:
    """Initializer that draws IN the parameter's dtype (the stock ones draw
    float32 and cast: a transient float32 copy of each leaf)."""

    def __init__(self, std=None, const=None):
        self.std, self.const = std, const

    def __call__(self, param, block=None):
        v = param._value
        if self.const is not None:
            param.set_value(jnp.full(v.shape, self.const, v.dtype))
        else:
            param.set_value(jax.random.normal(
                _random.get_rng_key(), v.shape, v.dtype) * jnp.asarray(self.std, v.dtype))
        return param


class _Mixer(nn.Layer):
    """A layer whose parameters are drawn in ``config.dtype`` with
    ``config.initializer_range``."""

    def __init__(self, config):
        super().__init__()
        self.config = config

    def _w(self, shape, dtype=None, const=None):
        init = _Drawn(const=const) if const is not None \
            else _Drawn(std=self.config.initializer_range)
        return self.create_parameter(list(shape), dtype=dtype or self.config.dtype,
                                     default_initializer=init)


@dataclass(frozen=True)
class Mamba2Sizes:
    hidden_size: int
    heads: int
    head_dim: int
    groups: int
    state: int          # N
    conv_kernel: int
    chunk_size: int     # the chunked scan's
    eps: float          # the gated RMSNorm's
    dtype: str
    initializer_range: float

    @property
    def inner(self):
        return self.heads * self.head_dim

    @property
    def conv_channels(self):
        return self.inner + 2 * self.groups * self.state


class Mamba2Mixer(_Mixer):
    def __init__(self, sizes: Mamba2Sizes):
        super().__init__(sizes)
        c = sizes
        h, inner, H = c.hidden_size, c.inner, c.heads
        self.in_proj = self._w((h, 2 * inner + 2 * c.groups * c.state + H))
        self.conv_weight = self._w((c.conv_kernel, c.conv_channels))  # taps first
        self.conv_bias = self._w((c.conv_channels,), const=0.0)
        # per-head scalars stay float32 whatever the weights' dtype
        self.dt_bias = self._w((H,), "float32", const=0.0)
        self.A_log = self._w((H,), "float32", const=0.0)
        self.D = self._w((H,), "float32", const=1.0)
        self.norm_weight = self._w((inner,), const=1.0)
        self.out_proj = self._w((inner, h))

    def state_shapes(self, state_dtype):
        """What a slot keeps: the SSM state with N on the sublanes and the
        heads of a group side by side on the lanes ([32, 128, 2 * 64] at
        64 heads of 64: `ops.ssm_update.state_shape`), and the
        convolution's."""
        c = self.config
        return (("ssm", _ssm.state_shape(c.heads, c.head_dim, c.state, c.groups),
                 jnp.dtype(state_dtype)),
                ("conv", ((c.conv_kernel - 1) * c.conv_channels,),
                 jnp.dtype(c.dtype)))

    def forward(self, u, cache):
        """u [B, S, h] raw; cache (ssm [slots, H/k, N, k*P], conv [slots,
        (K-1)*C], SlotRows).  Returns (out [B, S, h], (ssm, conv))."""
        c = self.config
        ssm_all, conv_all, sr = cache
        B, S, _ = u.shape
        inner = c.inner
        with jax.named_scope("ssm_mixer"):
            proj = u @ self.in_proj._value
            z = proj[..., :inner]
            xbc = proj[..., inner:inner + c.conv_channels]
            dt = proj[..., inner + c.conv_channels:]
            ssm, conv = (ssm_all, conv_all) if sr.rows is None \
                else (ssm_all[sr.rows], conv_all[sr.rows])
            if sr.fresh is not None:
                ssm = jnp.where(sr.fresh[:, None, None, None], 0, ssm)
                conv = jnp.where(sr.fresh[:, None], 0, conv)
            kw = dict(conv_weight=self.conv_weight._value,
                      conv_bias=self.conv_bias._value, a_log=self.A_log._value,
                      dt_bias=self.dt_bias._value, d_skip=self.D._value,
                      groups=c.groups, n_state=c.state)
            if S == 1:
                y, ssm, conv = _ssm.ssm_update(
                    ssm, conv, xbc[:, 0], dt[:, 0], valid=sr.n_valid > 0, **kw)
                y = y[:, None]
            else:
                y, ssm, conv = _ssm.ssm_chunk(
                    ssm, conv, xbc, dt, n_valid=sr.n_valid,
                    chunk_size=c.chunk_size, **kw)
            # gated RMSNorm over groups of inner / groups channels
            y = y * jax.nn.silu(z.astype(jnp.float32))
            yg = y.reshape(B, S, c.groups, inner // c.groups)
            yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                    + c.eps)
            y = yg.reshape(B, S, inner) * self.norm_weight._value.astype(jnp.float32)
            out = y.astype(u.dtype) @ self.out_proj._value
            if sr.rows is not None:
                ssm = ssm_all.at[sr.rows].set(ssm)
                conv = conv_all.at[sr.rows].set(conv)
        return out, (ssm, conv)
