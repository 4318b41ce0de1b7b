"""GPT family (learned-position causal decoder; complements LLaMA for the zoo)."""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from ..ops import lora as _lora
from ..tensor import creation
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-5
    tensor_parallel: bool = False

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=1024, hidden_size=128, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=512, max_position_embeddings=128)
        base.update(kw)
        return GPTConfig(**base)


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        tp = config.tensor_parallel
        self.ln_1 = nn.LayerNorm(h, epsilon=config.layer_norm_eps)
        self.num_heads = config.num_attention_heads
        self.head_dim = h // config.num_attention_heads
        if tp:
            self.qkv = ColumnParallelLinear(h, 3 * h, gather_output=False)
            self.proj = RowParallelLinear(h, h, input_is_parallel=True)
            self.fc_in = ColumnParallelLinear(h, config.intermediate_size, gather_output=False)
            self.fc_out = RowParallelLinear(config.intermediate_size, h, input_is_parallel=True)
        else:
            self.qkv = nn.Linear(h, 3 * h)
            self.proj = nn.Linear(h, h)
            self.fc_in = nn.Linear(h, config.intermediate_size)
            self.fc_out = nn.Linear(config.intermediate_size, h)
        self.ln_2 = nn.LayerNorm(h, epsilon=config.layer_norm_eps)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        self.attn_drop = config.attention_probs_dropout_prob

    def _proj_out(self, x, attn_flat):
        y = self.proj(attn_flat)
        d = _lora.apply_site("proj", attn_flat)
        return x + self.drop(y if d is None else y + d)

    def _mlp(self, x):
        h = self.ln_2(x)
        u = self.fc_in(h)
        d_in = _lora.apply_site("fc_in", h)
        if d_in is not None:  # multi-tenant LoRA epilogues (see forward)
            u = u + d_in
        g = F.gelu(u)
        y = self.fc_out(g)
        d_out = _lora.apply_site("fc_out", g)
        return x + self.drop(y if d_out is None else y + d_out)

    def forward(self, x, cache=None, use_cache=False):
        B, S = x.shape[0], x.shape[1]
        h = self.ln_1(x)
        qkv = self.qkv(h)
        dqkv = _lora.apply_site("qkv", h)
        if dqkv is not None:
            # multi-tenant LoRA epilogue: per-row adapter-page gathers add
            # the low-rank delta; zero-adapter rows gather page 0 (exact +0)
            qkv = qkv + dqkv
        qkv = qkv.reshape([B, S, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn_mask = None
        if cache is not None and len(cache) in (4, 6):
            # PAGED layout (kv_cache.py paged contract): scatter into the
            # global page pool, attend through the slot's page table —
            # ONE ragged paged Pallas kernel for any S on tile-aligned
            # shapes (decode, prefill chunks, spec-verify); gathered dense
            # math only for CPU-odd shapes
            from .kv_cache import paged_attention_update

            offset = cache[2]
            new_cache, attn = paged_attention_update(cache, q, k, v, offset)
            x = self._proj_out(x, attn.reshape([B, S, -1]))
            x = self._mlp(x)
            return x, new_cache
        elif cache is not None and len(cache) in (3, 5):
            # static head-major (k_buf, v_buf, pos) layout for the compiled
            # generate loop; the 5-tuple adds (k_scale, v_scale) for the int8
            # cache (kv_cache._quantize_kv) — the decode-attention kernel
            # dequantizes in VMEM and masks by the carried valid length
            from ..tensor.tensor import apply_op

            from ..ops.decode_attention import decode_attention
            from .kv_cache import update_plain_cache, update_quant_cache

            offset = cache[2]
            if len(cache) == 5:
                new_cache, k_q, v_q, k_sc, v_sc = update_quant_cache(
                    cache, k, v, offset, x.dtype)
                attn = apply_op(
                    lambda qq, kk, vv, ks, vs: decode_attention(
                        qq, kk, vv, offset, ks, vs),
                    (q, k_q, v_q, k_sc, v_sc), name="decode_attention")
            else:
                new_cache, k_b, v_b = update_plain_cache(cache, k, v, offset)
                attn = apply_op(
                    lambda qq, kk, vv: decode_attention(qq, kk, vv, offset),
                    (q, k_b, v_b), name="decode_attention")
            x = self._proj_out(x, attn.reshape([B, S, -1]))
            x = self._mlp(x)
            return x, new_cache
        elif cache is not None:
            from ..tensor import manipulation as M

            k = M.concat([cache[0], k], axis=1)
            v = M.concat([cache[1], v], axis=1)
            new_cache = (k, v)
            # queries are the last S positions of the concatenated sequence
            import jax.numpy as jnp

            from ..tensor.tensor import Tensor

            L = k.shape[1]
            jpos = jnp.arange(L)[None, :]
            qpos = jnp.arange(S)[:, None] + (L - S)
            attn_mask = Tensor(jnp.where(jpos <= qpos, 0.0, -1e9)[None, None])
        else:
            new_cache = (k, v) if use_cache else None
        attn = F.scaled_dot_product_attention(
            q, k, v, is_causal=attn_mask is None, attn_mask=attn_mask,
            dropout_p=self.attn_drop if self.training else 0.0,
        )
        x = self._proj_out(x, attn.reshape([B, S, -1]))
        x = self._mlp(x)
        if use_cache or cache is not None:
            return x, new_cache
        return x


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        Emb = VocabParallelEmbedding if config.tensor_parallel else nn.Embedding
        self.wte = Emb(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings, config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        self.h = nn.LayerList([GPTBlock(config) for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)

    def forward(self, input_ids, caches=None, use_cache=False):
        S = input_ids.shape[1]
        use_cache = use_cache or caches is not None
        if use_cache and caches is None:
            caches = [None] * len(self.h)
        if caches is not None and caches[0] is not None \
                and len(caches[0]) in (3, 4, 5, 6):
            # static or paged cache: the live offset is at [2] in every
            # fixed-capacity layout; the legacy growing (k, v) pair falls to
            # the elif, where the past length IS the k buffer's axis-1 extent
            import jax.numpy as jnp

            from ..tensor.tensor import Tensor

            off = caches[0][2]
            if getattr(off, "ndim", 0) >= 1:
                off = off[:, None]  # per-slot offsets (continuous batching)
            pos = Tensor(jnp.arange(S, dtype=jnp.int32)[None, :] + off)
        elif caches is not None and caches[0] is not None:
            off = caches[0][0].shape[1]
            pos = creation.arange(off, off + S, dtype="int32").unsqueeze(0)
        else:
            pos = creation.arange(S, dtype="int32").unsqueeze(0)
        x = self.drop(self.wte(input_ids) + self.wpe(pos))
        new_caches = [] if use_cache else None
        for i, block in enumerate(self.h):
            if use_cache:
                x, c = block(x, cache=caches[i], use_cache=True)
                new_caches.append(c)
            else:
                x = block(x)
        x = self.ln_f(x)
        if use_cache:
            return x, new_caches
        return x


class GPTForCausalLM(nn.Layer):
    _supports_quant_cache = True  # GPTBlock understands the 5-tuple
    _supports_paged_cache = True  # ... and the paged 4/6-tuples

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tensor_parallel:
            self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                                has_bias=False, gather_output=True)
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.gpt(input_ids))
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]), ignore_index=-100,
            )
            return loss, logits
        return logits

    def generate_step(self, input_ids, caches=None):
        """Prefill (caches=None) or single-token decode step."""
        hidden, caches = self.gpt(input_ids, caches=caches, use_cache=True)
        return self.lm_head(hidden[:, -1:]), caches

    def verify_step(self, input_ids, caches):
        """Speculative-decoding verify: full-ladder logits [B, S, V] for
        S = K+1 tokens scored in one pass (see llama.py)."""
        hidden, caches = self.gpt(input_ids, caches=caches, use_cache=True)
        return self.lm_head(hidden), caches

    def prefill_chunk_step(self, input_ids, caches, last_index):
        """One chunk of an incremental paged prefill (see llama.py)."""
        import jax

        from ..tensor.tensor import apply_op

        hidden, caches = self.gpt(input_ids, caches=caches, use_cache=True)
        last = apply_op(
            lambda h: jax.lax.dynamic_slice_in_dim(h, last_index, 1, 1),
            (hidden,), name="prefill_chunk_last")
        return self.lm_head(last), caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 pad_token_id=0, cache_dtype=None, kv_layout=None,
                 page_size=128, share_prefix=False, spec_k=0,
                 spec_drafter=None, adapter_id=None, adapters=None,
                 token_mask_fn=None):
        """Compiled decode loop on a static kv-cache (models/generation.py)."""
        from .generation import generate as _gen

        return _gen(self, input_ids, max_new_tokens, do_sample, temperature,
                    top_k, top_p, eos_token_id, pad_token_id,
                    cache_dtype=cache_dtype, kv_layout=kv_layout,
                    page_size=page_size, share_prefix=share_prefix,
                    spec_k=spec_k, spec_drafter=spec_drafter,
                    adapter_id=adapter_id, adapters=adapters,
                    token_mask_fn=token_mask_fn)
