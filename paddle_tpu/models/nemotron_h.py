"""Nemotron-H: a hybrid decoder of Mamba-2, mixture-of-experts and attention
layers (``model_type`` ``nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B).

Every layer is ``x <- x + mixer(RMSNorm(x))`` with ONE mixer, chosen by a
character of ``hybrid_override_pattern``:

  ``M``  Mamba-2: ``[z, xBC, dt] = W_in u``; ``xBC <- silu(conv(xBC) + b)``
         (causal, depthwise, ``conv_kernel`` taps) ``-> x, B, C``;
         ``dt <- softplus(dt + dt_bias)``; ``S_t = exp(dt A) S_{t-1} +
         dt x (x) B``; ``y = S C + D x``; ``y <- RMSNorm over groups of
         inner / n_groups of (y silu(z))``; ``W_out y``
  ``E``  experts: scores ``s = sigmoid(W_r u)`` in float32 over ALL
         ``n_routed_experts``, the ``num_experts_per_tok`` best by
         ``s + e_score_correction_bias``, weights ``s / (sum s + 1e-20) x
         routed_scaling_factor``; an expert is ``W_2 relu(W_1 u)^2`` (not
         gated); one shared expert is added for every token
  ``*``  grouped-query attention with the config's own ``head_dim``, causal,
         NO rotary embedding (the Mamba layers carry order; the family's
         modelling code applies none)

The expert layer is told which experts it holds (``experts_held``, a range):
it routes over all of them and computes the part its own give; what an
absent expert would add is left out (ops/moe_experts.py).  Parameters are
created in the configured dtype, leaf by leaf: nothing is built in float32
and cast.

Inference only: the serving surface ``LLMEngine`` calls (``generate_step``,
``prefill_chunk_step``, ``cache_kinds``) plus a cache-free ``forward``.  The
mixers are plain ``jnp`` over the parameters' arrays and record nothing on
the autograd tape; the scan has no backward pass yet (ROADMAP R5).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework import random as _random
from ..ops import moe_experts as _moe
from ..ops import ssm_update as _ssm
from ..tensor.tensor import Tensor
from .kv_cache import CacheKind, SlotRows, paged_attention_update



@dataclass
class NemotronHConfig:
    """The published keys of ``config.json`` (same names), plus
    ``experts_held``: the experts [lo, hi) this device holds, all by default.
    ``n_routed_experts`` stays the ROUTER's width."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    experts_held: tuple | None = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        pat = self.hybrid_override_pattern
        if len(pat) != self.num_hidden_layers or set(pat) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pat!r} must be {self.num_hidden_layers} "
                "characters of 'M', 'E' and '*'")
        lo, hi = self.experts_held or (0, self.n_routed_experts)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a range "
                             f"inside [0, {self.n_routed_experts})")
        self.experts_held = (int(lo), int(hi))

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                    hybrid_override_pattern="MEM*E", num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
                    mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                    chunk_size=8, n_routed_experts=8, num_experts_per_tok=2,
                    moe_intermediate_size=32,
                    moe_shared_expert_intermediate_size=48, dtype="float32")
        base.update(kw)
        return NemotronHConfig(**base)


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
    def __init__(self, config):
        super().__init__()
        self.config = config

    def _w(self, shape, dtype=None, const=None):
        init = _Drawn(const=const) if const is not None \
            else _Drawn(std=self.config.initializer_range)
        return self.create_parameter(list(shape), dtype=dtype or self.config.dtype,
                                     default_initializer=init)


def _rms(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


class NemotronHMamba2(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, inner, H = c.hidden_size, c.mamba_inner, c.mamba_num_heads
        self.in_proj = self._w((h, 2 * inner + 2 * c.n_groups * c.ssm_state_size + H))
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
        heads of a group side by side on the lanes ([32, 128, 2 * 64] as
        published: `ops.ssm_update.state_shape`), and the convolution's."""
        c = self.config
        return (("ssm", _ssm.state_shape(c.mamba_num_heads, c.mamba_head_dim,
                                         c.ssm_state_size, c.n_groups),
                 jnp.dtype(state_dtype)),
                ("conv", ((c.conv_kernel - 1) * c.conv_channels,),
                 jnp.dtype(c.dtype)))

    def forward(self, u, cache):
        """u [B, S, h] raw; cache (ssm [slots, H/k, N, k*P], conv [slots,
        (K-1)*C], SlotRows).  Returns (out [B, S, h], (ssm, conv))."""
        c = self.config
        ssm_all, conv_all, sr = cache
        B, S, _ = u.shape
        inner, H = c.mamba_inner, c.mamba_num_heads
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
                      groups=c.n_groups, n_state=c.ssm_state_size)
            if S == 1:
                y, ssm, conv = _ssm.ssm_update(
                    ssm, conv, xbc[:, 0], dt[:, 0], valid=sr.n_valid > 0, **kw)
                y = y[:, None]
            else:
                y, ssm, conv = _ssm.ssm_chunk(
                    ssm, conv, xbc, dt, n_valid=sr.n_valid,
                    chunk_size=c.chunk_size, **kw)
            # gated RMSNorm over groups of inner / n_groups channels
            y = y * jax.nn.silu(z.astype(jnp.float32))
            yg = y.reshape(B, S, c.n_groups, inner // c.n_groups)
            yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                    + c.layer_norm_epsilon)
            y = yg.reshape(B, S, inner) * self.norm_weight._value.astype(jnp.float32)
            out = y.astype(u.dtype) @ self.out_proj._value
            if sr.rows is not None:
                ssm = ssm_all.at[sr.rows].set(ssm)
                conv = conv_all.at[sr.rows].set(conv)
        return out, (ssm, conv)


class NemotronHMoE(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, F, Fs = c.hidden_size, c.moe_intermediate_size, \
            c.moe_shared_expert_intermediate_size
        lo, hi = c.experts_held
        self.gate_weight = self._w((h, c.n_routed_experts))
        self.e_score_correction_bias = self._w((c.n_routed_experts,), "float32",
                                               const=0.0)
        # both [held, F, h]: the hidden size, whole lanes, is minor in both
        self.experts_up = self._w((hi - lo, F, h))      # [out, in] an expert
        self.experts_down = self._w((hi - lo, F, h))    # [in, out] an expert
        self.shared_up = self._w((h, Fs))
        self.shared_down = self._w((Fs, h))

    def route(self, x):
        """x [T, h] -> (expert int32 [T, K], weight float32 [T, K])."""
        c = self.config
        return _moe.route(x, self.gate_weight._value,
                          self.e_score_correction_bias._value,
                          c.num_experts_per_tok, c.norm_topk_prob,
                          c.routed_scaling_factor)

    def forward(self, u, sr):
        """u [B, S, h] raw; sr SlotRows or None.  Returns (out, counts
        [held + 1] int32 over the real rows)."""
        B, S, h = u.shape
        x = u.reshape(B * S, h)
        idx, w = self.route(x)
        real = None if sr is None else \
            (jnp.arange(S)[None, :] < sr.n_valid[:, None]).reshape(B * S)
        routed, counts = _moe.moe_experts(
            x, self.experts_up._value, self.experts_down._value, idx, w,
            self.config.experts_held[0], real=real)
        with jax.named_scope("moe_shared"):
            hs = jnp.square(jnp.maximum(x @ self.shared_up._value, 0))
            shared = hs @ self.shared_down._value
        out = (routed + shared.astype(jnp.float32)).astype(u.dtype)
        return out.reshape(B, S, h), counts


class NemotronHAttention(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, D = c.hidden_size, c.head_dim
        self.q_proj = self._w((h, c.num_attention_heads * D))
        self.k_proj = self._w((h, c.num_key_value_heads * D))
        self.v_proj = self._w((h, c.num_key_value_heads * D))
        self.o_proj = self._w((c.num_attention_heads * D, h))

    def forward(self, u, cache):
        """cache: the paged tuple (k_pool, v_pool, pos, page_tbl[, scales]),
        or None for a whole sequence from position 0."""
        c = self.config
        B, S, _ = u.shape
        Hq, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        with jax.named_scope("attention"):
            q = (u @ self.q_proj._value).reshape(B, S, Hq, D)
            k = (u @ self.k_proj._value).reshape(B, S, Hkv, D)
            v = (u @ self.v_proj._value).reshape(B, S, Hkv, D)
            if cache is None:
                kk, vv = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (k, v))
                s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                               kk.astype(jnp.float32)) / D ** 0.5
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
                out = jnp.einsum("bhqk,bkhd->bqhd",
                                 jax.nn.softmax(s, axis=-1).astype(u.dtype), vv)
                new_cache = None
            else:
                new_cache, out = paged_attention_update(
                    cache, Tensor(q), Tensor(k), Tensor(v), cache[2])
                out = out._value
            return out.reshape(B, S, Hq * D) @ self.o_proj._value, new_cache


_MIXERS = {"M": NemotronHMamba2, "E": NemotronHMoE, "*": NemotronHAttention}


class NemotronHBlock(nn.Layer):
    def __init__(self, config, kind):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size, config.layer_norm_epsilon)
        self.mixer = _MIXERS[kind](config)

    def forward(self, x, cache):
        # the block's norm and residual: a scope of their own, so the
        # mixers' scopes hold the mixers alone
        with jax.named_scope("block_norm"):
            u = _rms(x, self.norm.weight._value, self.norm._epsilon)
        out, new = self.mixer(u, cache)
        with jax.named_scope("block_norm"):
            return x + out, new


class NemotronHForCausalLM(nn.Layer):
    _supports_paged_cache = True
    #: SSM state dtype (the convolution's state is in the weights' dtype)
    ssm_state_dtype = "float32"

    def __init__(self, config: NemotronHConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        draw = _Drawn(std=config.initializer_range)
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=draw)
        self.layers = nn.LayerList(
            [NemotronHBlock(config, k) for k in config.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size, config.layer_norm_epsilon)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=draw)
        dt = jnp.dtype(config.dtype)
        for p in (self.norm_f.weight, *(b.norm.weight for b in self.layers)):
            if p._value.dtype != dt:
                p._rebind(p._value.astype(dt))

    # ------------------------------------------------- what each layer keeps
    def cache_kinds(self):
        """One CacheKind a layer: page pools for attention, per-slot state
        for Mamba-2, nothing (but its pair counts) for experts."""
        c = self.config
        out = []
        for blk in self.layers:
            if blk.kind == "*":
                out.append(CacheKind("paged_kv", kv_heads=c.num_key_value_heads,
                                     head_dim=c.head_dim))
            elif blk.kind == "M":
                out.append(CacheKind(
                    "recurrent", state=blk.mixer.state_shapes(self.ssm_state_dtype)))
            else:
                lo, hi = c.experts_held
                out.append(CacheKind("none", experts_held=hi - lo,
                                     top_k=c.num_experts_per_tok))
        return out

    @property
    def num_params(self):
        import numpy as np

        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ------------------------------------------------------------- the stack
    def _run(self, ids, caches):
        with jax.named_scope("embed"):
            x = self.embed_tokens._value[ids]
        new = []
        for blk, c in zip(self.layers, caches):
            x, n = blk(x, c)
            new.append(n)
        with jax.named_scope("final_norm"):
            x = _rms(x, self.norm_f.weight._value, self.norm_f._epsilon)
        return x, new

    def _head(self, hidden):
        with jax.named_scope("lm_head"):
            return Tensor(hidden @ self.lm_head._value)

    @staticmethod
    def _ids(input_ids):
        return input_ids._value if isinstance(input_ids, Tensor) else input_ids

    def forward(self, input_ids):
        """Whole sequences from position 0, no cache: logits [B, T, V]."""
        ids = self._ids(input_ids)
        B, T = ids.shape
        sr = SlotRows(None, None, jnp.full((B,), T, jnp.int32))
        caches = []
        for blk in self.layers:
            if blk.kind == "M":
                caches.append(tuple(
                    jnp.zeros((B,) + shape, dt)
                    for _, shape, dt in blk.mixer.state_shapes(self.ssm_state_dtype))
                    + (sr,))
            else:
                caches.append(sr if blk.kind == "E" else None)
        hidden, _ = self._run(ids, caches)
        return self._head(hidden)

    def generate_step(self, input_ids, caches=None):
        """One decode token a row through the caches the engine hands in
        (per layer: the paged tuple, (state..., SlotRows), or SlotRows)."""
        if caches is None:
            raise ValueError("NemotronHForCausalLM decodes through the serving "
                             "engine's caches (LLMEngine, kv_layout='paged')")
        hidden, new = self._run(self._ids(input_ids), caches)
        return self._head(hidden[:, -1:]), new

    def prefill_chunk_step(self, input_ids, caches, last_index):
        """One chunk of an incremental prefill; logits at `last_index`."""
        hidden, new = self._run(self._ids(input_ids), caches)
        last = jax.lax.dynamic_slice_in_dim(hidden, last_index, 1, 1)
        return self._head(last), new
