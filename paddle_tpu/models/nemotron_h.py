"""Nemotron-H: a hybrid decoder of Mamba-2, mixture-of-experts and attention
layers (``model_type`` ``nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B).

Every layer is ``x <- x + mixer(RMSNorm(x))`` with ONE mixer, chosen by a
character of ``hybrid_override_pattern``:

  ``M``  Mamba-2 (models/mamba2.py, shared with Granite-4.0-H):
         ``[z, xBC, dt] = W_in u``; ``xBC <- silu(conv(xBC) + b)``
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
from ..ops import moe_experts as _moe
from ..tensor.tensor import Tensor
from .kv_cache import CacheKind, SlotRows, paged_attention_update
from .mamba2 import Mamba2Mixer, Mamba2Sizes, _Drawn, _Mixer


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


def _rms(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


class NemotronHMamba2(Mamba2Mixer):
    """The shared Mamba-2 mixer (models/mamba2.py) at a config's sizes."""

    def __init__(self, config):
        super().__init__(Mamba2Sizes(
            hidden_size=config.hidden_size, heads=config.mamba_num_heads,
            head_dim=config.mamba_head_dim, groups=config.n_groups,
            state=config.ssm_state_size, conv_kernel=config.conv_kernel,
            chunk_size=config.chunk_size, eps=config.layer_norm_epsilon,
            dtype=config.dtype, initializer_range=config.initializer_range))


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
    """Causal GQA with the config's own ``head_dim`` and no positional
    encoding; softmax scale ``scale``, 1/sqrt(head_dim) when None."""

    def __init__(self, config, scale=None):
        super().__init__(config)
        self.scale = scale
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
                               kk.astype(jnp.float32))
                s = s / D ** 0.5 if self.scale is None else s * self.scale
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
                out = jnp.einsum("bhqk,bkhd->bqhd",
                                 jax.nn.softmax(s, axis=-1).astype(u.dtype), vv)
                new_cache = None
            else:
                new_cache, out = paged_attention_update(
                    cache, Tensor(q), Tensor(k), Tensor(v), cache[2],
                    scale=self.scale)
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
