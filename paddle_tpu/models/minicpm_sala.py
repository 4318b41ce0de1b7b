"""MiniCPM-SALA: a decoder that mixes block-sparse attention and lightning
(linear) attention layers (``model_type`` ``minicpm_sala``; openbmb, 9B).

``mixer_types`` names each layer's mixer; every layer is MiniCPM's muP
residual pair

    h <- h + (scale_depth / sqrt(depth)) mixer(RMS(h))
    h <- h + (scale_depth / sqrt(depth)) W_down(silu(W_gate x) * W_up x),  x = RMS(h)

with ``depth`` the PUBLISHED number of layers (``residual_depth``) whatever
depth is run; ``h_0 = scale_emb E[id]`` and ``logits = W_head (RMS(h_L) /
(hidden / dim_model_base))``.

  ``lightning-attn``  q = RoPE(RMS_q(W_q x)), k = RoPE(RMS_k(W_k x)) (norm a
      head, rotate-half pairs), v = W_v x; a head keeps S_t = lambda_h S_{t-1}
      + k_t^T v_t in float32 and reads o_t = q_t S_t / sqrt(D); y =
      W_o(sigmoid(W_g x) * RMS_o(concat_h o_t))   (ops/lightning_attention.py)
  ``minicpm4``        q = RMS_q(W_q x), k = RMS_k(W_k x), v = W_v x, NO rotary
      embedding; each query attends the key blocks a selection over mean-pooled
      "compressed keys" picks for it (block 0, the last `window_size` tokens,
      the best of the rest; every block up to `dense_len`); y =
      W_o(sigmoid(W_g x) * concat_h a_h)           (ops/sparse_attention.py)

What the published config does not give is in ``MiniCPMSALAConfig``'s
defaults and named there: the decay slopes, the sparse sizes, the state's
dtype.  Parameters are created in the configured dtype leaf by leaf (a
float32 build of the 9B model would not fit a chip).  Inference only: the
serving surface ``LLMEngine`` calls plus a cache-free ``forward``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import lightning_attention as _la
from ..ops import sparse_attention as _sa
from ..tensor.tensor import Tensor
from .kv_cache import CacheKind, SlotRows, SparsePaged, _paged_scatter
from .nemotron_h import _Drawn, _Mixer, _rms

PUBLISHED_MIXERS = tuple(
    "minicpm4" if c == "s" else "lightning-attn"
    for c in "sLLLLLLLLsLLLLLLssLLLLsLLLLLLsss")
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclass
class MiniCPMSALAConfig:
    """The published keys of ``config.json`` (same names), then what it
    leaves out (``assumed`` in the benchmark's configuration file)."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple = PUBLISHED_MIXERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    initializer_range: float = 0.02
    # ---- not in the published config
    residual_depth: int | None = None   # the depth under the root; None = run depth
    sparse: _sa.SparseSpec = field(default_factory=_sa.SparseSpec)
    lightning_chunk: int = 128          # tokens a step of the chunked scan
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types)
        if len(self.mixer_types) != self.num_hidden_layers \
                or set(self.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"mixer_types must name {self.num_hidden_layers} layers, each "
                f"{SPARSE!r} or {LIGHTNING!r}")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("lightning layers keep one key/value head a query "
                             "head (lightning_nkv == lightning_nh)")
        if isinstance(self.sparse, dict):
            self.sparse = _sa.SparseSpec(**self.sparse)
        if self.residual_depth is None:
            self.residual_depth = self.num_hidden_layers

    @property
    def residual_scale(self):
        return self.scale_depth / self.residual_depth ** 0.5

    @staticmethod
    def tiny(**kw):
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE),
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
            sparse=_sa.SparseSpec(kernel_size=4, kernel_stride=2, block_size=4,
                                  topk=4, init_blocks=1, window_size=6,
                                  dense_len=24),
            lightning_chunk=8, dtype="float32")
        base.update(kw)
        return MiniCPMSALAConfig(**base)


def _rope(x, pos, theta):
    """Rotate-half pairs (x_i, x_{i + D/2}).  x [B, S, H, D]; pos int32 [B]
    the position of each row's first token."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    t = (pos[:, None] + jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
         ).astype(jnp.float32)
    ang = t[..., None, None] * inv                       # [B, S, 1, D/2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


class MiniCPMSALALightning(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, inner, D = c.hidden_size, c.lightning_nh * c.lightning_head_dim, \
            c.lightning_head_dim
        self.q_proj = self._w((h, inner))
        self.k_proj = self._w((h, inner))
        self.v_proj = self._w((h, inner))
        self.g_proj = self._w((h, inner))
        self.o_proj = self._w((inner, h))
        self.q_norm = self._w((D,), const=1.0)
        self.k_norm = self._w((D,), const=1.0)
        self.o_norm = self._w((inner,), const=1.0)

    def state_shapes(self):
        """A slot keeps S [N, P] a head: N on the sublanes, the value width
        on the lanes, as the state pass wants it.  float32 always: the one
        precision the configuration states for the state."""
        c = self.config
        D = c.lightning_head_dim
        return (("lightning", (c.lightning_nh, D, D), jnp.float32),)

    def forward(self, u, cache):
        """u [B, S, h] raw; cache (state [slots, H, N, P], SlotRows with
        `pos`).  Returns (out, (state,))."""
        c = self.config
        state_all, sr = cache
        B, S, _ = u.shape
        H, D = c.lightning_nh, c.lightning_head_dim
        eps = c.rms_norm_eps
        with jax.named_scope("lightning_mixer"):
            q = _rms((u @ self.q_proj._value).reshape(B, S, H, D),
                     self.q_norm._value, eps)
            k = _rms((u @ self.k_proj._value).reshape(B, S, H, D),
                     self.k_norm._value, eps)
            v = (u @ self.v_proj._value).reshape(B, S, H, D)
            q, k = _rope(q, sr.pos, c.rope_theta), _rope(k, sr.pos, c.rope_theta)
            state = state_all if sr.rows is None else state_all[sr.rows]
            if sr.fresh is not None:
                state = jnp.where(sr.fresh[:, None, None, None], 0, state)
            lam = _la.log_decay(H)
            if S == 1:
                o, state = _la.lightning_update(
                    state, q[:, 0], k[:, 0], v[:, 0], lam, sr.n_valid > 0)
                o = o[:, None]
            else:
                o, state = _la.lightning_chunk(
                    state, q, k, v, lam, sr.n_valid, c.lightning_chunk)
            o = (o / D ** 0.5).reshape(B, S, H * D)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
                * self.o_norm._value.astype(jnp.float32)
            gate = jax.nn.sigmoid((u @ self.g_proj._value).astype(jnp.float32))
            out = (gate * o).astype(u.dtype) @ self.o_proj._value
            if sr.rows is not None:
                state = state_all.at[sr.rows].set(state)
        return out, (state,)


class MiniCPMSALASparseAttention(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        c = config
        h, D = c.hidden_size, c.head_dim
        self.q_proj = self._w((h, c.num_attention_heads * D))
        self.k_proj = self._w((h, c.num_key_value_heads * D))
        self.v_proj = self._w((h, c.num_key_value_heads * D))
        self.g_proj = self._w((h, c.num_attention_heads * D))
        self.o_proj = self._w((c.num_attention_heads * D, h))
        self.q_norm = self._w((D,), const=1.0)
        self.k_norm = self._w((D,), const=1.0)

    def forward(self, u, cache: SparsePaged):
        """Returns (out, (k, v, ck pools, counts)); counts int32 [3]: this
        call's real queries, the blocks of their contexts, the blocks their
        selected lists hold (`select_blocks`' `picked`)."""
        c = self.config
        spec = c.sparse
        B, S, _ = u.shape
        Hq, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        k_pool, v_pool, ck_pool, pos, tbl, sr = cache
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        with jax.named_scope("sparse_attention"):
            q = _rms((u @ self.q_proj._value).reshape(B, S, Hq, D),
                     self.q_norm._value, c.rms_norm_eps)
            k = _rms((u @ self.k_proj._value).reshape(B, S, Hkv, D),
                     self.k_norm._value, c.rms_norm_eps)
            v = (u @ self.v_proj._value).reshape(B, S, Hkv, D)
            hm = lambda a, pool: jnp.transpose(a, (0, 2, 1, 3)).astype(pool.dtype)  # noqa: E731
            k_pool = _paged_scatter(k_pool, hm(k, k_pool), pos, tbl)
            v_pool = _paged_scatter(v_pool, hm(v, v_pool), pos, tbl)
            ck_pool = _sa.write_compressed(ck_pool, k_pool, pos, tbl, S, spec)
            q = q.astype(k_pool.dtype)
            if S == 1:
                n = pos + 1
                idx, cnt, picked = _sa.select_blocks(
                    q.reshape(B, 1, Hkv, Hq // Hkv, D),
                    _sa.gather_compressed(ck_pool, tbl), n[:, None], spec)
                real = sr.n_valid > 0
                a = _sa.sparse_paged_attention(
                    q[:, 0], k_pool, v_pool, tbl, idx[:, 0],
                    jnp.where(real, cnt[:, 0], 0), n, spec)[:, None]
                picked = picked[:, 0]
            else:
                if B != 1:
                    raise ValueError("a block of queries is one sequence's "
                                     "prefill chunk (batch 1)")
                a, picked = _sa.sparse_chunk_attention(
                    q, k_pool, v_pool, ck_pool, pos, tbl, spec)
                n = pos[0] + jnp.arange(1, S + 1, dtype=jnp.int32)
                real = jnp.arange(S) < sr.n_valid[0]
            counts = jnp.stack([
                jnp.sum(real), jnp.sum(jnp.where(real, -(-n // spec.block_size), 0)),
                jnp.sum(jnp.where(real, picked, 0))]).astype(jnp.int32)
            gate = jax.nn.sigmoid((u @ self.g_proj._value).astype(jnp.float32))
            out = (gate * a.reshape(B, S, Hq * D).astype(jnp.float32)
                   ).astype(u.dtype) @ self.o_proj._value
        return out, (k_pool, v_pool, ck_pool, counts)


class MiniCPMSALAMLP(_Mixer):
    def __init__(self, config):
        super().__init__(config)
        h, F = config.hidden_size, config.intermediate_size
        self.gate_proj = self._w((h, F))
        self.up_proj = self._w((h, F))
        self.down_proj = self._w((F, h))

    def forward(self, x):
        with jax.named_scope("mlp"):
            return (jax.nn.silu(x @ self.gate_proj._value)
                    * (x @ self.up_proj._value)) @ self.down_proj._value


class MiniCPMSALABlock(nn.Layer):
    def __init__(self, config, kind):
        super().__init__()
        self.kind = kind
        self.scale = config.residual_scale
        self.input_norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mixer = (MiniCPMSALASparseAttention if kind == SPARSE
                      else MiniCPMSALALightning)(config)
        self.post_norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = MiniCPMSALAMLP(config)

    def forward(self, x, cache):
        eps = self.input_norm._epsilon
        # the block's norms and scaled residuals: a scope of their own
        with jax.named_scope("block_norm"):
            u = _rms(x, self.input_norm.weight._value, eps)
        out, new = self.mixer(u, cache)
        with jax.named_scope("block_norm"):
            x = x + (out.astype(jnp.float32) * self.scale).astype(x.dtype)
            u = _rms(x, self.post_norm.weight._value, eps)
        y = self.mlp(u)
        with jax.named_scope("block_norm"):
            return x + (y.astype(jnp.float32) * self.scale).astype(x.dtype), new


class MiniCPMSALAForCausalLM(nn.Layer):
    _supports_paged_cache = True

    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        draw = _Drawn(std=config.initializer_range)
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=draw)
        self.layers = nn.LayerList(
            [MiniCPMSALABlock(config, k) for k in config.mixer_types])
        self.norm_f = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=draw)
        dt = jnp.dtype(config.dtype)
        norms = [self.norm_f.weight] + [
            n.weight for b in self.layers for n in (b.input_norm, b.post_norm)]
        for p in norms:
            if p._value.dtype != dt:
                p._rebind(p._value.astype(dt))

    # ------------------------------------------------- what each layer keeps
    def cache_kinds(self):
        """Page pools with the compressed keys beside them for the sparse
        layers, a state matrix a head a SLOT for the lightning layers."""
        c = self.config
        return [
            CacheKind("paged_kv", kv_heads=c.num_key_value_heads,
                      head_dim=c.head_dim, compressed=c.sparse.kernel_stride)
            if blk.kind == SPARSE else
            CacheKind("recurrent", state=blk.mixer.state_shapes())
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
                 * c.scale_emb).astype(self.embed_tokens._value.dtype)
        new = []
        for blk, cache in zip(self.layers, caches):
            x, n = blk(x, cache)
            new.append(n)
        with jax.named_scope("final_norm"):
            x = _rms(x, self.norm_f.weight._value, self.norm_f._epsilon)
            x = (x.astype(jnp.float32)
                 / (c.hidden_size / c.dim_model_base)).astype(x.dtype)
        return x, new

    def _head(self, hidden):
        with jax.named_scope("lm_head"):
            return Tensor(hidden @ self.lm_head._value)

    @staticmethod
    def _ids(input_ids):
        return input_ids._value if isinstance(input_ids, Tensor) else input_ids

    def forward(self, input_ids):
        """Whole sequences from position 0, no cache the caller sees: logits
        [B, T, V].  Each row runs as ONE prefill chunk over pools of its own
        (the serving path's code, so the two cannot drift apart)."""
        c = self.config
        ids = self._ids(input_ids)
        B, T = ids.shape
        sp = c.sparse
        ps = sp.block_size
        M = -(-T // ps)
        dt = self.embed_tokens._value.dtype
        out = []
        for b in range(B):
            sr = SlotRows(None, None, jnp.full((1,), T, jnp.int32),
                          jnp.zeros((1,), jnp.int32))
            tbl = jnp.arange(1, M + 1, dtype=jnp.int32)[None, :]
            caches = []
            for blk in self.layers:
                if blk.kind == SPARSE:
                    shape = (M + 1, c.num_key_value_heads, ps, c.head_dim)
                    ck = (M + 1, c.num_key_value_heads, ps // sp.kernel_stride,
                          c.head_dim)
                    caches.append(SparsePaged(
                        jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                        jnp.zeros(ck, dt), jnp.zeros((1,), jnp.int32), tbl, sr))
                else:
                    caches.append(tuple(
                        jnp.zeros((1,) + shape, sdt)
                        for _, shape, sdt in blk.mixer.state_shapes())
                        + (sr,))
            pad = (-T) % max(1, min(c.lightning_chunk, T))
            row = jnp.pad(ids[b:b + 1], ((0, 0), (0, pad)))
            hidden, _ = self._run(row, caches)
            out.append(hidden[:, :T])
        return self._head(jnp.concatenate(out, axis=0))

    def generate_step(self, input_ids, caches=None):
        """One decode token a row through the caches the engine hands in
        (per layer: a SparsePaged, or (state, SlotRows))."""
        if caches is None:
            raise ValueError("MiniCPMSALAForCausalLM decodes through the "
                             "serving engine's caches (LLMEngine)")
        hidden, new = self._run(self._ids(input_ids), caches)
        return self._head(hidden[:, -1:]), new

    def prefill_chunk_step(self, input_ids, caches, last_index):
        """One chunk of an incremental prefill; logits at `last_index`."""
        hidden, new = self._run(self._ids(input_ids), caches)
        last = jax.lax.dynamic_slice_in_dim(hidden, last_index, 1, 1)
        return self._head(last), new
