"""LLaMA-2 family (BASELINE config #5: LLaMA-2-7B hybrid tp+pp+sharding-stage-2).

Reference gap: the Paddle snapshot has no LLaMA (PaddleNLP's lives outside the repo);
this is the TPU-native flagship decoder: RMSNorm + RoPE + GQA + SwiGLU, with
Megatron-style TP expressed as sharding annotations (mp_layers) so the SAME module
runs dense on one chip or tp/dp/pp/sharded on a mesh via ShardedTrainStep /
PipelineTrainStep.  Attention routes through F.scaled_dot_product_attention, which
selects the Pallas flash kernel on TPU for long sequences.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import functional as F
from ..ops import lora as _lora
from ..ops.decode_attention import _note
from ..tensor.tensor import Tensor, apply_op
from ..tensor import manipulation as M
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)
from ..distributed.sharding_ctx import annotate, constraint


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    dtype: str = "float32"
    # parallel plan (consumed via sharding annotations)
    tensor_parallel: bool = True
    sequence_parallel: bool = False

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=1024, hidden_size=256, intermediate_size=688,
                    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
                    max_position_embeddings=512)
        base.update(kw)
        return LlamaConfig(**base)


def _rope_cache(head_dim, max_pos, theta):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_pos, dtype=np.float32)
    freqs = np.outer(t, inv)  # [T, D/2]
    return jnp.asarray(np.cos(freqs)), jnp.asarray(np.sin(freqs))


from .kv_cache import (  # noqa: E402  (shared cache layouts; re-exported
    _quantize_kv,         # for backward compat — tests import from here)
    paged_attention_update,
    paged_mixed_update,
    update_plain_cache,
    update_quant_cache,
)


def _note_qkv(one_product, fusable):
    """Count the projection path at trace time, once an attention call site
    a compiled program, where the attention dispatch is counted
    (``llm_attn_kernel_total``, ``stats()["attn_dispatch"]``)."""
    if one_product:
        _note("qkv_one_product", "plain_linear")
    else:
        _note("qkv_three_products", "static_rows" if fusable else "wrapped")


def _static_decode_mask(offset, S, L):
    """Additive causal+padding mask for a static-cache step: queries at
    pos offset+i see keys j <= offset+i; the padded tail is masked."""
    jpos = jnp.arange(L)[None, :]
    qpos = jnp.arange(S)[:, None] + offset
    return jnp.where(jpos <= qpos, 0.0, -1e9)[None, None]


def apply_rope(x, cos, sin, position_offset=0):
    """x: [B, S, H, D] raw array; rotate-half RoPE — pairs (x_i, x_{i+D/2}).
    Contiguous half-splits instead of stride-2 interleaving: on TPU the
    lane-dim strided gather + stack materializes [., D/2, 2] copies in the
    decode scan body (each one a serial kernel dispatch); the half-split
    form fuses clean.  Attention scores are identical under either pairing
    since q and k share the permutation.
    position_offset may be a traced scalar (static-cache decode), a
    PER-BATCH [B] vector (continuous-batching slots at different depths) or
    every token's own position [B, S]."""
    S, D = x.shape[1], x.shape[-1]
    if isinstance(position_offset, (int, np.integer)):
        c = cos[position_offset:position_offset + S]
        s = sin[position_offset:position_offset + S]
    elif getattr(position_offset, "ndim", 0) == 2:
        # a position a token [B, S] (a tick's chunk rows and decode rows
        # side by side): gather [B, S, D/2] position rows
        c = cos[position_offset][:, :, None, :]  # [B,S,1,D/2]
        s = sin[position_offset][:, :, None, :]
        x1, x2 = x[..., :D // 2], x[..., D // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    elif getattr(position_offset, "ndim", 0) == 1:
        # per-slot offsets
        return apply_rope(x, cos, sin,
                          position_offset[:, None] + jnp.arange(S)[None, :])
    else:
        c = jax.lax.dynamic_slice_in_dim(cos, position_offset, S, 0)
        s = jax.lax.dynamic_slice_in_dim(sin, position_offset, S, 0)
    c = c[None, :, None, :]  # [1,S,1,D/2]
    s = s[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        tp = config.tensor_parallel
        Lin = ColumnParallelLinear if tp else nn.Linear
        mk = (lambda i, o: ColumnParallelLinear(i, o, has_bias=False, gather_output=False)) if tp \
            else (lambda i, o: nn.Linear(i, o, bias_attr=False))
        self.q_proj = mk(self.hidden_size, self.num_heads * self.head_dim)
        self.k_proj = mk(self.hidden_size, self.num_kv_heads * self.head_dim)
        self.v_proj = mk(self.hidden_size, self.num_kv_heads * self.head_dim)
        if tp:
            self.o_proj = RowParallelLinear(self.num_heads * self.head_dim, self.hidden_size,
                                            has_bias=False, input_is_parallel=True)
        else:
            self.o_proj = nn.Linear(self.num_heads * self.head_dim, self.hidden_size, bias_attr=False)

    def _o(self, out):
        with jax.named_scope("o_proj"):
            y = self.o_proj(out)
            d = _lora.apply_site("o", out)
            return y if d is None else y + d

    def _qkv(self, hidden_states, one_product):
        """The three projections with their LoRA epilogues, under the
        ``qkv_proj`` scope: ONE product over the three weights where
        ``one_product`` (see forward), else three."""
        nq = self.num_heads * self.head_dim
        nkv = self.num_kv_heads * self.head_dim
        with jax.named_scope("qkv_proj"):
            if one_product:
                # the TPU compiler fuses the weights' concat into the product,
                # which reads each weight byte once where it lies.  Three
                # products of many rows whose [rows, heads, D] reshape folds
                # into them become convolutions over the heads that
                # transpose every weight first, a copy per call
                def _fused_qkv(h, wq, wk, wv):
                    w = jnp.concatenate([wq, wk, wv], axis=1)
                    return h @ w.astype(h.dtype)

                qkv = apply_op(_fused_qkv,
                               (hidden_states, self.q_proj.weight,
                                self.k_proj.weight, self.v_proj.weight),
                               name="fused_qkv")
                q = qkv[:, :, :nq]
                k = qkv[:, :, nq:nq + nkv]
                v = qkv[:, :, nq + nkv:]
            else:
                q = self.q_proj(hidden_states)
                k = self.k_proj(hidden_states)
                v = self.v_proj(hidden_states)
            dq = _lora.apply_site("q", hidden_states)
            if dq is not None:
                # multi-tenant LoRA epilogue: per-row adapter-page gathers add
                # the low-rank delta; zero-adapter rows gather page 0 (exact +0)
                q = q + dq
                k = k + _lora.apply_site("k", hidden_states)
                v = v + _lora.apply_site("v", hidden_states)
        return q, k, v

    def _qkv_rope(self, hidden_states, rope, offset, one_product):
        """The normed rows [B, S, hidden] to the rotated q and k and the v,
        [B, S, heads, D]: the products, the split into heads, and rope at
        ``offset`` (any form apply_rope takes), all on the rows."""
        B, S = hidden_states.shape[0], hidden_states.shape[1]
        q, k, v = self._qkv(hidden_states, one_product)
        q = q.reshape([B, S, self.num_heads, self.head_dim])
        k = k.reshape([B, S, self.num_kv_heads, self.head_dim])
        v = v.reshape([B, S, self.num_kv_heads, self.head_dim])
        rot = lambda a, c, s: apply_rope(a, c, s, offset)  # noqa: E731
        q = apply_op(rot, (q, *rope), name="rope")
        k = apply_op(rot, (k, *rope), name="rope")
        return q, k, v

    def forward(self, hidden_states, rope, attn_mask=None, cache=None,
                use_cache=False, chunk=None):
        """rope: (cos, sin) Tensors shared at LlamaModel level (one copy, not 32).
        cache=None with use_cache=True is the prefill step: the returned cache is
        this call's own k/v.  ``chunk`` = (off, page_row): hidden_states
        [1, C + B] lays a prefill chunk's rows and a paged cache's B decode
        rows side by side (mixed_step) — one pass through the projections,
        each kind's own positions, pages and attention between them."""
        B, S = hidden_states.shape[0], hidden_states.shape[1]
        # a 3-tuple cache (k_buf, v_buf, pos) is the STATIC layout used by the
        # compiled generate() loop: fixed-size HEAD-MAJOR [B, H, L, D] buffers
        # + in-place scatter, so every decode step has identical shapes and
        # compiles once.  A 5-tuple (k_q, v_q, pos, k_scale, v_scale) is the
        # int8-quantized variant: per-(head, token) absmax scales — HALF the
        # cache HBM footprint AND half the decode stream (the Pallas decode
        # kernel dequantizes in VMEM; ops/decode_attention.py).  The 4/6-tuple
        # PAGED variants route through a global page pool + per-slot page
        # tables (kv_cache.py paged contract): same math, but capacity scales
        # with actual sequence lengths — the serving engine's layout.
        static_cache = cache is not None and len(cache) in (3, 5)
        quant_cache = cache is not None and len(cache) == 5
        paged_cache = cache is not None and len(cache) in (4, 6)
        fusable = (type(self.q_proj) is nn.Linear and type(self.k_proj) is nn.Linear
                   and type(self.v_proj) is nn.Linear  # not wrapped (quant etc.)
                   and all(getattr(p, "bias", None) is None
                           for p in (self.q_proj, self.k_proj, self.v_proj)))
        # one product for a decode step and for every paged call (decode,
        # chunk, mixed, verify); the training forward and a static cache's
        # prefill keep three
        one_product = fusable and (S == 1 or paged_cache)
        if chunk is not None:
            # every token's own position: the chunk's run from its offset,
            # each decode row's from its slot's
            offset = jnp.concatenate([
                chunk[0] + jnp.arange(S - cache[2].shape[0], dtype=jnp.int32),
                cache[2]])[None, :]
        elif static_cache or paged_cache:
            offset = cache[2]
        else:
            offset = cache[0].shape[1] if cache is not None else 0
        if use_cache:
            _note_qkv(one_product, fusable)
        q, k, v = self._qkv_rope(hidden_states, rope, offset, one_product)

        if paged_cache and attn_mask is None:
            # paged decode / chunked-prefill / spec-verify path: scatter
            # into the page pool, then attend through the page table — ONE
            # ragged paged Pallas kernel for any S on tile-aligned shapes
            # (S=1 decode, prefill chunks, the K+1 verify ladder); gathered
            # dense math only for CPU-odd shapes
            # (llm_attn_kernel_total{path,reason} counts the dispatch)
            if chunk is not None:
                new_cache, out = paged_mixed_update(cache, chunk, q, k, v)
            else:
                new_cache, out = paged_attention_update(cache, q, k, v, offset)
            out = out.reshape([B, S, self.num_heads * self.head_dim])
            out = self._o(out)
            if use_cache:
                return out, new_cache
            return out

        if static_cache and attn_mask is None:
            # decode hot path: single-query attention straight off the
            # head-major static cache (Pallas on TPU, dense math elsewhere)
            from ..ops.decode_attention import decode_attention

            if quant_cache:
                new_cache, k_q, v_q, k_sc, v_sc = update_quant_cache(
                    cache, k, v, offset, hidden_states.dtype)
                out = apply_op(
                    lambda qq, kk, vv, ks, vs: decode_attention(
                        qq, kk, vv, offset, ks, vs),
                    (q, k_q, v_q, k_sc, v_sc), name="decode_attention")
            else:
                new_cache, k_b, v_b = update_plain_cache(cache, k, v, offset)
                out = apply_op(
                    lambda qq, kk, vv: decode_attention(qq, kk, vv, offset),
                    (q, k_b, v_b), name="decode_attention")
            out = out.reshape([B, S, self.num_heads * self.head_dim])
            out = self._o(out)
            if use_cache:
                return out, new_cache
            return out

        if static_cache:
            # external mask with a static cache: dense path over the
            # head-major buffers brought back to [B, L, H, D]
            if quant_cache:
                new_cache, k_q, v_q, k_sc, v_sc = update_quant_cache(
                    cache, k, v, offset, hidden_states.dtype)
                deq = lambda b, s, dt=hidden_states.dtype: jnp.transpose(  # noqa: E731
                    b.astype(dt) * s.astype(dt)[..., None], (0, 2, 1, 3))
                k = apply_op(deq, (k_q, k_sc), name="kv_dequant")
                v = apply_op(deq, (v_q, v_sc), name="kv_dequant")
            else:
                new_cache, k_b, v_b = update_plain_cache(cache, k, v, offset)
                tohm = lambda b: jnp.transpose(b, (0, 2, 1, 3))  # noqa: E731
                k = apply_op(tohm, (k_b,), name="kv_unpack")
                v = apply_op(tohm, (v_b,), name="kv_unpack")
        else:
            if cache is not None:
                k = M.concat([cache[0], k], axis=1)
                v = M.concat([cache[1], v], axis=1)
            new_cache = (k, v) if use_cache else None

        # GQA: repeat kv heads to match q heads
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = apply_op(lambda a: jnp.repeat(a, rep, axis=2), (k,), name="gqa_repeat")
            v = apply_op(lambda a: jnp.repeat(a, rep, axis=2), (v,), name="gqa_repeat")

        if self.config.sequence_parallel and attn_mask is None and cache is None:
            # context parallelism (§5.7): ring attention across the 'sep' mesh
            # axis — the sequence stays sharded through the whole layer stack
            from ..ops.sequence_parallel import ring_attention_global

            out = apply_op(
                lambda a, b, c: ring_attention_global(
                    a, b, c, causal=True,
                    use_flash=self.config.use_flash_attention),
                (q, k, v), name="ring_attention")
        else:
            backend = "auto" if self.config.use_flash_attention else "math"
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None, backend=backend,
            )
        out = out.reshape([B, S, self.num_heads * self.head_dim])
        out = self._o(out)
        if use_cache:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        tp = config.tensor_parallel
        h, inter = config.hidden_size, config.intermediate_size
        if tp:
            self.gate_proj = ColumnParallelLinear(h, inter, has_bias=False, gather_output=False)
            self.up_proj = ColumnParallelLinear(h, inter, has_bias=False, gather_output=False)
            self.down_proj = RowParallelLinear(inter, h, has_bias=False, input_is_parallel=True)
        else:
            self.gate_proj = nn.Linear(h, inter, bias_attr=False)
            self.up_proj = nn.Linear(h, inter, bias_attr=False)
            self.down_proj = nn.Linear(inter, h, bias_attr=False)

    def forward(self, x):
        if x.shape[1] == 1 and type(self.gate_proj) is nn.Linear \
                and type(self.up_proj) is nn.Linear \
                and getattr(self.gate_proj, "bias", None) is None \
                and getattr(self.up_proj, "bias", None) is None:
            # decode step: fuse gate+up into one gemv (see fused_qkv note)
            def _fused_gu(h, wg, wu):
                w = jnp.concatenate([wg, wu], axis=1)
                return h @ w.astype(h.dtype)

            gu = apply_op(_fused_gu, (x, self.gate_proj.weight, self.up_proj.weight),
                          name="fused_gate_up")
            inter = self.gate_proj.weight.shape[1]
            g, u = gu[:, :, :inter], gu[:, :, inter:]
        else:
            g, u = self.gate_proj(x), self.up_proj(x)
        dg = _lora.apply_site("gate", x)
        if dg is not None:  # multi-tenant LoRA epilogues (see LlamaAttention)
            g = g + dg
            u = u + _lora.apply_site("up", x)
        h = F.silu(g) * u
        y = self.down_proj(h)
        dd = _lora.apply_site("down", h)
        return y if dd is None else y + dd


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, x, rope, attn_mask=None, cache=None, use_cache=False,
                chunk=None):
        # named scopes label the ops for the profiler (op_name
        # ".../attention/...", ".../mlp/..."); they change no computation
        with jax.named_scope("attention"):
            h = self.input_layernorm(x)
            if use_cache:
                attn_out, new_cache = self.self_attn(
                    h, rope, attn_mask, cache, use_cache=True, chunk=chunk)
            else:
                attn_out = self.self_attn(h, rope, attn_mask)
            x = x + attn_out
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        if use_cache:
            return x, new_cache
        return x


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_cache(config.hidden_size // config.num_attention_heads,
                               config.max_position_embeddings, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, caches=None, use_cache=False,
                chunk=None):
        """caches=[None]*num_layers (or caches=None with use_cache=True) is the
        prefill bootstrap; each entry is then a (k, v) pair for the decode steps.
        ``chunk``: see LlamaAttention.forward (paged caches only)."""
        use_cache = use_cache or caches is not None
        if use_cache and caches is None:
            caches = [None] * len(self.layers)
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        rope = (self.rope_cos, self.rope_sin)
        # static-cache decode needs NO mask tensor: the decode-attention
        # kernel masks by the carried valid length (ops/decode_attention.py)
        new_caches = [] if use_cache else None
        for i, layer in enumerate(self.layers):
            if use_cache:
                x, c = layer(x, rope, attn_mask, caches[i], use_cache=True,
                             chunk=chunk)
                new_caches.append(c)
            else:
                x = layer(x, rope, attn_mask)
        with jax.named_scope("final_norm"):
            x = self.norm(x)
        if use_cache:
            return x, new_caches
        return x


class LlamaForCausalLM(nn.Layer):
    _supports_quant_cache = True  # LlamaAttention understands the 5-tuple
    _supports_paged_cache = True  # ... and the paged 4/6-tuples

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tensor_parallel:
            self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                                has_bias=False, gather_output=True)
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)

    def _head(self, hidden):
        with jax.named_scope("lm_head"):
            return self.lm_head(hidden)

    def forward(self, input_ids, labels=None):
        hidden = self.llama(input_ids)
        logits = self._head(hidden)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]),
                ignore_index=-100,
            )
            return loss, logits
        return logits

    @property
    def num_params(self):
        import numpy as np

        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def generate_step(self, input_ids, caches=None):
        """Prefill (caches=None) or single-token decode step (inference path)."""
        hidden, caches = self.llama(input_ids, caches=caches, use_cache=True)
        return self._head(hidden[:, -1:]), caches

    def verify_step(self, input_ids, caches):
        """Speculative-decoding verify: score S = K+1 tokens in ONE pass
        through the decode cache path (on the paged layout this is the
        ragged Pallas kernel — the verify ladder is just another ragged
        query block), returning the logits at EVERY position [B, S, V] —
        generate_step keeps only the last, but the accept/rollback
        decision needs the whole ladder (ops/sampling spec_accept)."""
        hidden, caches = self.llama(input_ids, caches=caches, use_cache=True)
        return self._head(hidden), caches

    def prefill_chunk_step(self, input_ids, caches, last_index):
        """One CHUNK of an incremental (paged) prefill: input_ids [B, C] are
        the next C prompt tokens of each row (pad-padded past `last_index`
        on the final chunk), caches carry the paged pools + page tables with
        pos = tokens already prefilled.  Returns (logits [B, 1, V] at
        `last_index`, caches) — the logits only matter on the final chunk;
        earlier chunks pay one [B, 1, V] head gemv for shape stability
        (llm_server.py compiles exactly ONE chunk program for every prompt
        length).  On tile-aligned shapes the chunk's
        attention is the ragged paged Pallas kernel — the per-slot chunk
        offset rides the kernel's prefetched lengths vector."""
        hidden, caches = self.llama(input_ids, caches=caches, use_cache=True)
        last = apply_op(
            lambda h: jax.lax.dynamic_slice_in_dim(h, last_index, 1, 1),
            (hidden,), name="prefill_chunk_last")
        return self._head(last), caches

    def mixed_step(self, chunk_ids, input_ids, caches, chunk, last_index):
        """A serving tick's two kinds of row through ONE weight pass:
        ``chunk_ids`` [1, C], the next chunk of one slot's prompt (as
        prefill_chunk_step takes it: ``chunk`` = (off [1], page_row [1, M]),
        the tokens already prefilled and the slot's page-table row), and
        ``input_ids`` [B, 1], every slot's decode token (as generate_step
        takes it: ``caches`` carry pos [B] and the page table [B, M]).  The
        C + B rows are laid side by side through every norm, projection and
        MLP; only rope, the page writes and the attention tell them apart.
        The chunk's slot must not decode in the same call (its row of the
        table masked to the trash page, as for any slot between chunks).
        Returns (decode logits [B, 1, V], the chunk's logits [1, 1, V] at
        ``last_index``, caches)."""
        C = chunk_ids.shape[1]
        ids = M.concat([chunk_ids, input_ids.reshape([1, -1])], axis=1)
        hidden, caches = self.llama(ids, caches=caches, use_cache=True,
                                    chunk=chunk)
        rows = apply_op(
            lambda h: jnp.concatenate([
                jax.lax.dynamic_slice_in_dim(h[0], last_index, 1, 0),
                h[0, C:]])[:, None],
            (hidden,), name="mixed_head_rows")
        logits = self._head(rows)  # [1 + B, 1, V]: one pass over the head
        return logits[1:], logits[:1], caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 pad_token_id=0, cache_dtype=None, kv_layout=None,
                 page_size=128, share_prefix=False, spec_k=0,
                 spec_drafter=None, adapter_id=None, adapters=None,
                 token_mask_fn=None):
        """Compiled autoregressive decoding on a static kv-cache — one XLA
        program for prefill + the whole token scan (models/generation.py).
        cache_dtype='int8' halves the kv-cache HBM footprint;
        kv_layout='paged' decodes through the paged pool + page-table
        layout (the serving engine's cache) for parity/benchmarking;
        share_prefix=True additionally aliases the batch's common prompt
        prefix onto shared physical pages (the prefix-cache read path);
        spec_k=K enables speculative decoding (K drafts verified per
        compiled step; greedy output is bitwise identical to spec_k=0);
        adapter_id=/adapters= routes the call through a paged LoRA
        adapter pool (models/lora.py); token_mask_fn= applies a compiled
        token automaton (inference/constrain.py) for constrained
        decoding."""
        from .generation import generate as _gen

        return _gen(self, input_ids, max_new_tokens, do_sample, temperature,
                    top_k, top_p, eos_token_id, pad_token_id,
                    cache_dtype=cache_dtype, kv_layout=kv_layout,
                    page_size=page_size, share_prefix=share_prefix,
                    spec_k=spec_k, spec_drafter=spec_drafter,
                    adapter_id=adapter_id, adapters=adapters,
                    token_mask_fn=token_mask_fn)
