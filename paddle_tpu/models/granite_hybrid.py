"""Granite-4.0-H: a hybrid decoder of Mamba-2 and attention layers, each
followed by a gated MLP, under muP-style multipliers (``model_type``
``granitemoehybrid``; IBM Granite-4.0-H-Micro, -Small).

  ``x = embedding_multiplier * E[ids]``
  every layer: ``x <- x + residual_multiplier * mixer(RMSNorm(x))``, then
               ``x <- x + residual_multiplier * (moe(v) + W_out (silu(g) * u))``
               with ``v = RMSNorm(x)``, ``[g, u] = W_in v`` (the first half is
               the gate: the shared MLP)
  ``logits = RMSNorm(x) E^T / logits_scaling``: the head IS the embedding

``moe`` is there only with ``num_local_experts`` > 0 (Small; Micro has the
shared MLP alone): the router's logits ``W_r v`` in float32 over all
``num_local_experts``, the ``num_experts_per_tok`` largest, their weights a
softmax over those alone; expert e adds ``g_e W2_e (silu(Wg_e v) * W1_e v)``.
The layer is told which experts it holds (``experts_held``, a range, as in
Nemotron-H): it routes over all of them and computes the part its own give;
what an absent expert would add is left out (ops/moe_experts.py).

The mixer is a character of ``layer_types``: ``mamba``, the Mamba-2 mixer of
models/mamba2.py (shared with Nemotron-H; at one group its gated RMSNorm
runs over every inner channel), or ``attention``, Nemotron-H's causal GQA
with no positional encoding (``position_embedding_type`` ``nope``) and the
softmax scale ``attention_multiplier``.  Heads of 64 keep two a 128-lane
row of the page pool (ops/decode_attention.py: ``pool_layout``), so the
paged kernel serves them at the heads' own bytes.

Inference only, with the serving surface ``LLMEngine`` calls
(``generate_step``, ``prefill_chunk_step``, ``cache_kinds``) plus a
cache-free ``forward``.  Parameters are created in the configured dtype,
leaf by leaf.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import moe_experts as _moe
from ..ops.decode_attention import pool_layout
from ..tensor.tensor import Tensor
from .kv_cache import CacheKind, SlotRows
from .mamba2 import Mamba2Mixer, Mamba2Sizes, _Drawn, _Mixer
from .nemotron_h import NemotronHAttention, _rms

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
MAMBA, ATTENTION = "mamba", "attention"


@dataclass
class GraniteHybridConfig:
    """The published keys of ``config.json`` under their own names, plus
    ``experts_held``: the experts [lo, hi) this device holds, all by default.
    ``num_local_experts`` stays the ROUTER's width; ``intermediate_size`` is
    one routed expert's."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple = _PERIOD * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    shared_intermediate_size: int = 8192
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    position_embedding_type: str = "nope"
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    intermediate_size: int = 0
    experts_held: tuple | None = None
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        E = self.num_local_experts
        if E > 0:
            if not 0 < self.num_experts_per_tok <= E or self.intermediate_size <= 0:
                raise ValueError(
                    f"num_local_experts={E} needs num_experts_per_tok in "
                    f"1..{E} and an intermediate_size, got "
                    f"{self.num_experts_per_tok} and {self.intermediate_size}")
            lo, hi = self.experts_held or (0, E)
            if not 0 <= lo < hi <= E:
                raise ValueError(f"experts_held {self.experts_held} is not a "
                                 f"range inside [0, {E})")
            self.experts_held = (int(lo), int(hi))
        elif self.experts_held is not None:
            raise ValueError("experts_held needs num_local_experts > 0")
        if self.position_embedding_type != "nope":
            raise ValueError(
                f"position_embedding_type={self.position_embedding_type!r}: "
                "only 'nope' is implemented (no rotary embedding)")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"mamba_n_groups={self.mamba_n_groups} does not divide "
                f"mamba_n_heads={self.mamba_n_heads}")
        as_built = dict(tie_word_embeddings=True, hidden_act="silu",
                        mamba_conv_bias=True, mamba_proj_bias=False,
                        attention_bias=False)
        other = {k: getattr(self, k) for k, v in as_built.items()
                 if getattr(self, k) != v}
        if other:
            raise ValueError(f"{other}: only {as_built} (Micro's) is implemented")
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must be {self.num_hidden_layers} entries of "
                f"'mamba' and 'attention', got {self.layer_types}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def mamba_sizes(self):
        return Mamba2Sizes(
            hidden_size=self.hidden_size, heads=self.mamba_n_heads,
            head_dim=self.mamba_d_head, groups=self.mamba_n_groups,
            state=self.mamba_d_state, conv_kernel=self.mamba_d_conv,
            chunk_size=self.mamba_chunk_size, eps=self.rms_norm_eps,
            dtype=self.dtype, initializer_range=self.initializer_range)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                    layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA),
                    num_attention_heads=4, num_key_value_heads=2,
                    mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
                    mamba_chunk_size=8, shared_intermediate_size=96,
                    dtype="float32")
        base.update(kw)
        return GraniteHybridConfig(**base)


class GraniteHybridMLP(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        h, F = config.hidden_size, config.shared_intermediate_size
        self.input_linear = self._w((h, 2 * F))   # [gate | up]
        self.output_linear = self._w((F, h))

    def forward(self, x):
        F = self.config.shared_intermediate_size
        with jax.named_scope("mlp"):
            gu = x @ self.input_linear._value
            return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ self.output_linear._value


class GraniteHybridMoE(_Mixer):
    """The routed experts this device holds, [held, F, h] each matrix (the
    hidden size, whole lanes, minor in all three: ops/moe_experts.py)."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        h, F = c.hidden_size, c.intermediate_size
        lo, hi = c.experts_held
        self.gate_weight = self._w((h, c.num_local_experts))   # the router
        self.experts_gate = self._w((hi - lo, F, h))    # [out, in] an expert
        self.experts_up = self._w((hi - lo, F, h))      # [out, in]
        self.experts_down = self._w((hi - lo, F, h))    # [in, out]

    def forward(self, u, sr):
        """u [B, S, h]; sr SlotRows or None.  Returns (float32 [B, S, h]: the
        held experts' part, counts [held + 1] int32 over the real rows)."""
        B, S, h = u.shape
        x = u.reshape(B * S, h)
        idx, w = _moe.route_softmax(x, self.gate_weight._value,
                                    self.config.num_experts_per_tok)
        real = None if sr is None else \
            (jnp.arange(S)[None, :] < sr.n_valid[:, None]).reshape(B * S)
        out, counts = _moe.moe_experts(
            x, self.experts_up._value, self.experts_down._value, idx, w,
            self.config.experts_held[0], real=real,
            w_gate=self.experts_gate._value)
        return out.reshape(B, S, h), counts


class GraniteHybridBlock(nn.Layer):
    def __init__(self, config, kind):
        super().__init__()
        self.kind = kind
        self.scale = config.residual_multiplier
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.mixer = Mamba2Mixer(config.mamba_sizes()) if kind == MAMBA \
            else NemotronHAttention(config, scale=config.attention_multiplier)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.block_sparse_moe = GraniteHybridMoE(config) \
            if config.num_local_experts else None
        self.shared_mlp = GraniteHybridMLP(config)

    def forward(self, x, cache, sr=None):
        """With experts, `sr` marks the real rows for their counts, and the
        counts come back LAST after what the mixer keeps."""
        eps = self.input_layernorm._epsilon
        # the block's norms and scaled residuals: a scope of their own
        with jax.named_scope("block_norm"):
            u = _rms(x, self.input_layernorm.weight._value, eps)
        out, new = self.mixer(u, cache)
        with jax.named_scope("block_norm"):
            x = x + (out.astype(jnp.float32) * self.scale).astype(x.dtype)
            u = _rms(x, self.post_attention_layernorm.weight._value, eps)
        y = self.shared_mlp(u)
        if self.block_sparse_moe is not None:
            routed, counts = self.block_sparse_moe(u, sr)
            y = routed + y.astype(jnp.float32)
            new = (() if new is None else tuple(new)) + (counts,)
        with jax.named_scope("block_norm"):
            return x + (y.astype(jnp.float32) * self.scale).astype(x.dtype), new


class GraniteHybridForCausalLM(nn.Layer):
    _supports_paged_cache = True
    #: SSM state dtype (the convolution's state is in the weights' dtype)
    ssm_state_dtype = "float32"

    def __init__(self, config: GraniteHybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        # one [vocab, hidden] leaf: the input table and the output head
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=_Drawn(std=config.initializer_range))
        self.layers = nn.LayerList(
            [GraniteHybridBlock(config, k) for k in config.layer_types])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        dt = jnp.dtype(config.dtype)
        norms = [self.norm.weight] + [
            n.weight for b in self.layers
            for n in (b.input_layernorm, b.post_attention_layernorm)]
        for p in norms:
            if p._value.dtype != dt:
                p._rebind(p._value.astype(dt))

    # ------------------------------------------------- what each layer keeps
    def cache_kinds(self):
        """Page pools for attention (two 64-wide heads a 128-lane row at
        Micro's sizes: `pool_layout`), per-slot state for Mamba-2; with
        routed experts every layer also reports its pairs."""
        c = self.config
        rows, width = pool_layout(c.num_key_value_heads, c.head_dim)
        moe = {} if not c.num_local_experts else dict(
            experts_held=c.experts_held[1] - c.experts_held[0],
            top_k=c.num_experts_per_tok)
        return [CacheKind("paged_kv", kv_heads=rows, head_dim=width, **moe)
                if blk.kind == ATTENTION else
                CacheKind("recurrent",
                          state=blk.mixer.state_shapes(self.ssm_state_dtype), **moe)
                for blk in self.layers]

    @property
    def num_params(self):
        import numpy as np

        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ------------------------------------------------------------- the stack
    def _run(self, ids, caches):
        c = self.config
        with jax.named_scope("embed"):
            x = (self.embed_tokens._value[ids].astype(jnp.float32)
                 * c.embedding_multiplier).astype(self.embed_tokens._value.dtype)
        # the batch's rows, as the recurrent layers are handed them: an
        # expert layer counts its pairs over the real ones
        sr = next((c[-1] for c in caches if isinstance(c, tuple) and c
                   and isinstance(c[-1], SlotRows)), None)
        new = []
        for blk, cache in zip(self.layers, caches):
            x, n = blk(x, cache, sr)
            new.append(n)
        with jax.named_scope("final_norm"):
            x = _rms(x, self.norm.weight._value, self.norm._epsilon)
        return x, new

    def _head(self, hidden):
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bth,vh->btv", hidden, self.embed_tokens._value)
            return Tensor((logits.astype(jnp.float32)
                           / self.config.logits_scaling).astype(logits.dtype))

    @staticmethod
    def _ids(input_ids):
        return input_ids._value if isinstance(input_ids, Tensor) else input_ids

    def forward(self, input_ids):
        """Whole sequences from position 0, no cache: logits [B, T, V]."""
        ids = self._ids(input_ids)
        B, T = ids.shape
        sr = SlotRows(None, None, jnp.full((B,), T, jnp.int32))
        caches = [tuple(jnp.zeros((B,) + shape, dt) for _, shape, dt in
                        blk.mixer.state_shapes(self.ssm_state_dtype)) + (sr,)
                  if blk.kind == MAMBA else None for blk in self.layers]
        hidden, _ = self._run(ids, caches)
        return self._head(hidden)

    def generate_step(self, input_ids, caches=None):
        """One decode token a row through the caches the engine hands in
        (per layer: the paged tuple, or (state..., SlotRows))."""
        if caches is None:
            raise ValueError("GraniteHybridForCausalLM decodes through the "
                             "serving engine's caches (LLMEngine, "
                             "kv_layout='paged')")
        hidden, new = self._run(self._ids(input_ids), caches)
        return self._head(hidden[:, -1:]), new

    def prefill_chunk_step(self, input_ids, caches, last_index):
        """One chunk of an incremental prefill; logits at `last_index`."""
        hidden, new = self._run(self._ids(input_ids), caches)
        last = jax.lax.dynamic_slice_in_dim(hidden, last_index, 1, 1)
        return self._head(last), new
