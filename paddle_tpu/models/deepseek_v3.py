"""DeepSeek-V3-shaped decoders (``model_type`` ``deepseek_v3``; Kanana-2-30B-
A3B): multi-head latent attention and gated routed experts with shared ones.

Every layer is ``x <- x + Attn(RMSNorm(x))`` then ``x <- x + FFN(RMSNorm(x))``.
For a token at position t with normed input u:

  queries   q = W_q u, a head's q = [q^nope ; q^rope]; q^rope <- RoPE(q^rope, t)
            (``q_lora_rank`` null: one matrix)
  latent    [c ; k^rope] = W_kva u; c <- RMSNorm(c) (over ``kv_lora_rank``);
            k^rope <- RoPE(k^rope, t): ONE rotary key a token, shared by all
            heads.  The cache holds (c, k^rope) and nothing else
  RoPE      ``rope_interleave``: adjacent pairs (2i, 2i+1) rotated by
            t theta^(-2i/d) (the family's code de-interleaves q and k by one
            permutation first; scores do not change under a permutation the
            two share, and the weights keep the published row order)
  attention [k^nope_h ; v_h] = W_kvb,h c; s = (q^nope_h . k^nope_h + q^rope_h .
            k^rope) / sqrt(d_nope + d_rope), causal soft-max, W_o [o_1; ..]
  dense FFN (the first ``first_k_dense_replace`` layers) W_down(silu(W_gate u)
            * W_up u)
  MoE FFN   scores sigmoid(W_r u) in float32 over all experts, the
            ``num_experts_per_tok`` best by score + ``e_score_correction_bias``
            (``n_group`` = ``topk_group`` = 1: no group limit), weights
            s / (sum s + 1e-20) x ``routed_scaling_factor``; a routed expert
            is the gated MLP of width ``moe_intermediate_size``; the
            ``n_shared_experts`` shared ones are ONE gated MLP of their summed
            width, added for every token.  No capacity, no dropped token

Decode runs the ABSORBED form over the latent pages (ops/latent_attention.py:
``q~_h = W^K_h^T q^nope_h`` scores the latents, the values are the latents,
``W^V_h`` is applied after); a prefill chunk runs whichever form is less work
for its length.  The expert layer is told which experts it holds
(``experts_held``), routes over all of them through the router Nemotron-H
routes through (``ops.moe_experts.route``) and computes its own experts' part
in the gated form of the same op.  Parameters are created in the configured
dtype, leaf by leaf.  Keys the code does not implement raise at construction.

Inference only: the serving surface ``LLMEngine`` calls (``cache_kinds``,
``generate_step``, ``prefill_chunk_step``) plus a cache-free ``forward``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import latent_attention as _la
from ..ops import moe_experts as _moe
from ..tensor.tensor import Tensor
from .kv_cache import CacheKind, LatentPaged, SlotRows
from .nemotron_h import _Drawn, _Mixer, _rms


@dataclass
class DeepseekV3Config:
    """The published keys of ``config.json`` (same names), plus
    ``experts_held``: the experts [lo, hi) this device holds, all by default
    (``n_routed_experts`` stays the ROUTER's width)."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    n_shared_experts: int = 2
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    experts_held: tuple | None = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        # what the code does not implement is refused, never ignored
        unread = {
            "q_lora_rank": (self.q_lora_rank, None),
            "n_group": (self.n_group, 1), "topk_group": (self.topk_group, 1),
            "rope_scaling": (self.rope_scaling, None),
            "rope_interleave": (self.rope_interleave, True),
            "scoring_func": (self.scoring_func, "sigmoid"),
            "topk_method": (self.topk_method, "noaux_tc"),
            "hidden_act": (self.hidden_act, "silu"),
            "moe_layer_freq": (self.moe_layer_freq, 1),
            "attention_bias": (self.attention_bias, False),
            "tie_word_embeddings": (self.tie_word_embeddings, False),
            "num_key_value_heads": (self.num_key_value_heads,
                                    self.num_attention_heads),
        }
        for k, (got, only) in unread.items():
            if got != only:
                raise ValueError(
                    f"DeepseekV3Config: {k}={got!r} is not implemented (only "
                    f"{only!r}: a compressed query path, group-limited "
                    "routing, scaled rotary embeddings and the like are "
                    "other mechanisms)")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the depth")
        lo, hi = self.experts_held or (0, self.n_routed_experts)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a range "
                             f"inside [0, {self.n_routed_experts})")
        self.experts_held = (int(lo), int(hi))

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    num_attention_heads=4, num_key_value_heads=4,
                    n_shared_experts=2, n_routed_experts=8,
                    num_experts_per_tok=2, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    max_position_embeddings=512, dtype="float32")
        base.update(kw)
        return DeepseekV3Config(**base)


class DeepseekV3Attention(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, H = c.hidden_size, c.num_attention_heads
        self.q_proj = self._w((h, H * c.qk_head_dim))
        self.kv_a_proj_with_mqa = self._w((h, c.kv_lora_rank + c.qk_rope_head_dim))
        self.kv_a_layernorm = self._w((c.kv_lora_rank,), const=1.0)
        # [latent, heads x (nope + v)]: a head's W^K then its W^V, transposed
        self.kv_b_proj = self._w((c.kv_lora_rank,
                                  H * (c.qk_nope_head_dim + c.v_head_dim)))
        self.o_proj = self._w((H * c.v_head_dim, h))

    def forward(self, u, cache: LatentPaged):
        """u [B, S, h] raw.  Returns (out, (pool, counts)); counts int32 [2]:
        this call's real queries and the tokens of their contexts."""
        c = self.config
        B, S, _ = u.shape
        H, dn, dr, dv, dc = (c.num_attention_heads, c.qk_nope_head_dim,
                             c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank)
        pool, pos, tbl, sr = cache
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        tpos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        scale = 1.0 / c.qk_head_dim ** 0.5
        w_kvb = self.kv_b_proj._value.reshape(dc, H, dn + dv)
        with jax.named_scope("latent_mixer"):
            q = (u @ self.q_proj._value).reshape(B, S, H, dn + dr)
            q_nope = q[..., :dn]
            q_rope = _la.rope_interleaved(q[..., dn:], tpos, c.rope_theta)
            kva = u @ self.kv_a_proj_with_mqa._value
            lat = _rms(kva[..., :dc], self.kv_a_layernorm._value, c.rms_norm_eps)
            k_rope = _la.rope_interleaved(kva[..., dc:], tpos, c.rope_theta)
            pool = _la.write_latent(pool, lat, k_rope, pos, tbl)
            if S == 1:
                real = sr.n_valid > 0
                n = pos + 1
                with jax.named_scope("latent_absorb"):
                    q_abs = _la._einsum_f32("bhd,chd->bhc", q_nope[:, 0],
                                            w_kvb[..., :dn]).astype(u.dtype)
                o = _la.latent_decode_attention(
                    q_abs, q_rope[:, 0], pool, tbl, jnp.where(real, n, 0), scale)
                with jax.named_scope("latent_absorb"):
                    a = _la._einsum_f32("bhc,chd->bhd", o, w_kvb[..., dn:]
                                        ).astype(u.dtype)[:, None]
            else:
                if B != 1:
                    raise ValueError("a block of queries is one sequence's "
                                     "prefill chunk (batch 1)")
                a = _la.latent_chunk_attention(
                    q_nope[0], q_rope[0], pool, tbl, pos[0], w_kvb, dc, scale
                )[None].astype(u.dtype)
                n = tpos[0] + 1
                real = jnp.arange(S) < sr.n_valid[0]
            counts = jnp.stack([jnp.sum(real), jnp.sum(jnp.where(real, n, 0))]
                               ).astype(jnp.int32)
            out = a.reshape(B, S, H * dv) @ self.o_proj._value
        return out, (pool, counts)


class DeepseekV3MLP(_Mixer):
    """The gated MLP of a dense layer, and of the shared experts."""

    def __init__(self, config, width, scope):
        super().__init__(config)
        h = config.hidden_size
        self.scope = scope
        self.gate_proj = self._w((h, width))
        self.up_proj = self._w((h, width))
        self.down_proj = self._w((width, h))

    def forward(self, x):
        with jax.named_scope(self.scope):
            return (jax.nn.silu(x @ self.gate_proj._value)
                    * (x @ self.up_proj._value)) @ self.down_proj._value


class DeepseekV3MoE(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, F = c.hidden_size, c.moe_intermediate_size
        lo, hi = c.experts_held
        self.gate_weight = self._w((h, c.n_routed_experts))
        self.e_score_correction_bias = self._w((c.n_routed_experts,), "float32",
                                               const=0.0)
        # all [held, F, h]: the hidden size, whole lanes, is minor in each
        self.experts_gate = self._w((hi - lo, F, h))    # [out, in] an expert
        self.experts_up = self._w((hi - lo, F, h))      # [out, in]
        self.experts_down = self._w((hi - lo, F, h))    # [in, out]
        self.shared_experts = DeepseekV3MLP(c, c.n_shared_experts * F,
                                            "moe_shared")

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
            self.config.experts_held[0], real=real,
            w_gate=self.experts_gate._value)
        out = (routed + self.shared_experts(x).astype(jnp.float32)).astype(u.dtype)
        return out.reshape(B, S, h), counts


class DeepseekV3Block(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        self.is_moe = index >= config.first_k_dense_replace
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.mlp = DeepseekV3MoE(config) if self.is_moe else \
            DeepseekV3MLP(config, config.intermediate_size, "dense_mlp")

    def forward(self, x, cache: LatentPaged):
        """Returns (x, (pool, latent counts[, expert counts]))."""
        eps = self.input_layernorm._epsilon
        # the block's norms and residuals: a scope of their own
        with jax.named_scope("block_norm"):
            u = _rms(x, self.input_layernorm.weight._value, eps)
        out, new = self.self_attn(u, cache)
        with jax.named_scope("block_norm"):
            x = x + out
            u = _rms(x, self.post_attention_layernorm.weight._value, eps)
        if self.is_moe:
            y, counts = self.mlp(u, cache.rows)
            new = new + (counts,)
        else:
            y = self.mlp(u)
        with jax.named_scope("block_norm"):
            return x + y, new


class DeepseekV3ForCausalLM(nn.Layer):
    _supports_paged_cache = True

    def __init__(self, config: DeepseekV3Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        draw = _Drawn(std=config.initializer_range)
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=draw)
        self.layers = nn.LayerList(
            [DeepseekV3Block(config, i) for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=draw)
        dt = jnp.dtype(config.dtype)
        norms = [self.norm.weight] + [
            n.weight for b in self.layers
            for n in (b.input_layernorm, b.post_attention_layernorm)]
        for p in norms:
            if p._value.dtype != dt:
                p._rebind(p._value.astype(dt))

    # ------------------------------------------------- what each layer keeps
    def cache_kinds(self):
        """One latent page pool a layer; the expert layers also report their
        pairs."""
        c = self.config
        lo, hi = c.experts_held
        return [CacheKind("paged_latent", latent_dim=c.kv_lora_rank,
                          rope_dim=c.qk_rope_head_dim,
                          experts_held=(hi - lo) if blk.is_moe else 0,
                          top_k=c.num_experts_per_tok if blk.is_moe else 0)
                for blk in self.layers]

    @property
    def num_params(self):
        import numpy as np

        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # ------------------------------------------------------------- the stack
    def _run(self, ids, caches):
        with jax.named_scope("embed"):
            x = self.embed_tokens._value[ids]
        new = []
        for blk, cache in zip(self.layers, caches):
            x, n = blk(x, cache)
            new.append(n)
        with jax.named_scope("final_norm"):
            x = _rms(x, self.norm.weight._value, self.norm._epsilon)
        return x, new

    def _head(self, hidden):
        with jax.named_scope("lm_head"):
            return Tensor(hidden @ self.lm_head._value)

    @staticmethod
    def _ids(input_ids):
        return input_ids._value if isinstance(input_ids, Tensor) else input_ids

    def forward(self, input_ids):
        """Whole sequences from position 0, no cache the caller sees: logits
        [B, T, V].  Each row runs as ONE prefill chunk over a pool of its own
        (the serving path's code, so the two cannot drift apart)."""
        c = self.config
        ids = self._ids(input_ids)
        B, T = ids.shape
        page_size = 16
        M = -(-T // page_size)
        dt = self.embed_tokens._value.dtype
        width = _la.pool_width(c.kv_lora_rank, c.qk_rope_head_dim)
        out = []
        for b in range(B):
            sr = SlotRows(None, None, jnp.full((1,), T, jnp.int32))
            tbl = jnp.arange(1, M + 1, dtype=jnp.int32)[None, :]
            caches = [LatentPaged(jnp.zeros((M + 1, page_size, width), dt),
                                  jnp.zeros((1,), jnp.int32), tbl, sr)
                      for _ in self.layers]
            hidden, _ = self._run(ids[b:b + 1], caches)
            out.append(hidden)
        return self._head(jnp.concatenate(out, axis=0))

    def generate_step(self, input_ids, caches=None):
        """One decode token a row through the caches the engine hands in
        (a LatentPaged a layer)."""
        if caches is None:
            raise ValueError("DeepseekV3ForCausalLM decodes through the "
                             "serving engine's caches (LLMEngine)")
        hidden, new = self._run(self._ids(input_ids), caches)
        return self._head(hidden[:, -1:]), new

    def prefill_chunk_step(self, input_ids, caches, last_index):
        """One chunk of an incremental prefill; logits at `last_index`."""
        hidden, new = self._run(self._ids(input_ids), caches)
        last = jax.lax.dynamic_slice_in_dim(hidden, last_index, 1, 1)
        return self._head(last), new
