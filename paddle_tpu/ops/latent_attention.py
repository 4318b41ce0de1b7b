"""Multi-head latent attention (MLA, the DeepSeek-V2/V3 family's) over ONE
latent page pool a layer.

A token keeps, in all, its compressed latent ``c`` (``latent_dim`` values;
every head's keys and values are projections of it) and one rotary key
``k^rope`` (``rope_dim`` values) that all heads share.  The pool is
``[pages, page_size, width]``: a token's row is ``[c ; k^rope ; 0 ...]``
padded to whole lanes (``pool_width``: 512 + 64 -> 640 at the published
sizes; a row that is not whole lanes is padded to them by the device's tiled
layout anyway, and the pad being explicit zeros lets ONE matmul over the
whole row score both parts).  A page is one contiguous block, so a fetch is
one copy.

With ``W_kvb,h = [W^K_h ; W^V_h]`` (each ``[d, latent_dim]``) the scores and
outputs of head h are

    s_j = (q^nope_h . W^K_h c_j + q^rope_h . k^rope_j) * scale
    o_h = W^V_h sum_j p_j c_j

in two forms that give the same numbers:

  absorbed  q~_h = W^K_h^T q^nope_h scores the latents directly and the
            values are the latents: 2 H (latent + rope + latent) operations a
            (query, key) pair, nothing kept but the pool
  expanded  every key's k^nope_h and v_h are materialised first: 2 H (d_qk +
            d_v) a pair plus 2 latent H (d_nope + d_v) a key to expand it

``latent_decode_attention`` is the decode pass in the absorbed form: a Pallas
kernel (device events ``latent_attention``) that takes one row a grid step,
walks its page table ONCE, fetches the pages by explicit copies in groups of
``_GROUP`` (double buffered) and scores ALL heads against each group it has
fetched, the values being the first ``latent_dim`` lanes of the same tile;
the absorptions are matmuls around it (the caller's).  A gathered pass in
plain XLA serves the CPU and shapes off the tile (``kernel_ok``); the choice
is counted in ``llm_attn_kernel_total``.

``latent_chunk_attention`` is a prefill chunk: T queries of one sequence
against ``off + T`` latents, key blocks of ``_CHUNK_KEYS`` folded into an
online soft-max in plain XLA (a loop whose trip count is the context's, not
the table's).  It runs whichever form is less work for its T (``expanded_
wins``): expanding costs a fixed amount a context key, so many queries
amortise it and few do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._prng import interpret_default as _interpret_default
from .decode_attention import _note
from .sparse_attention import _einsum_f32

NEG_INF = -1e30
TRASH_PAGE = 0     # models.kv_cache.TRASH_PAGE: never allocated
LANES = 128
_GROUP = 4         # pages fetched and scored together by the decode kernel
_CHUNK_KEYS = 1024  # keys a block of the chunk's pass (whole pages)


def pool_width(latent_dim, rope_dim):
    """Lanes a token's row takes: latent and rotary key, padded to whole
    lanes."""
    return -(-(latent_dim + rope_dim) // LANES) * LANES


def rope_interleaved(x, pos, theta):
    """Rotate ADJACENT pairs (x_2i, x_2i+1) by pos * theta^(-2i/D)
    (``rope_interleave``).  x [..., T, H, D] or [..., T, D] with `pos` int32
    broadcastable to x's leading axes through T."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[..., None] * inv             # [..., T, D/2]
    while ang.ndim < x.ndim:
        ang = ang[..., None, :]                                # over the heads
    c, s = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (D // 2, 2))
    a, b = x32[..., 0], x32[..., 1]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape
                                                                ).astype(x.dtype)


def write_latent(pool, c, k_rope, pos, page_tbl):
    """Scatter the rows [c ; k_rope ; 0] of S new tokens a batch row into the
    pool at positions pos .. pos + S - 1, routed through the page table (a
    position past the table, or an idle row's masked table, lands in the
    trash page).  pool [P, ps, W]; c [B, S, dc]; k_rope [B, S, dr]."""
    from ..models.kv_cache import _token_pages_rows

    B, S, _ = c.shape
    W = pool.shape[-1]
    row = jnp.concatenate([c, k_rope], axis=-1).astype(pool.dtype)
    row = jnp.pad(row, ((0, 0), (0, 0), (0, W - row.shape[-1])))
    page, r = _token_pages_rows(pos, page_tbl, S, pool.shape[1], page_tbl.shape[1])
    return pool.at[page, r].set(row)


# ------------------------------------------------------------------ decode
def _decode_kernel(len_ref, pt_ref, q_ref, pool_hbm, o_ref, buf, sem, *,
                   ps, M, dc, scale):
    b = pl.program_id(0)
    n = len_ref[b]
    KB = _GROUP * ps
    ngrp = (n + KB - 1) // KB
    npages = (n + ps - 1) // ps
    H = q_ref.shape[1]

    def copies(slot, grp):
        out = []
        for e in range(_GROUP):
            i = grp * _GROUP + e
            # an entry past the row's last page fetches the trash page: whole
            # groups hold finite rows and the mask drops them
            page = jnp.where(i < npages,
                             pt_ref[b * M + jnp.minimum(i, M - 1)], TRASH_PAGE)
            out.append(pltpu.make_async_copy(
                pool_hbm.at[page], buf.at[slot, pl.ds(e * ps, ps)],
                sem.at[slot, e]))
        return out

    @pl.when(ngrp > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    col = jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)

    def body(grp, carry):
        m, l, acc = carry
        slot = grp % 2

        @pl.when(grp + 1 < ngrp)
        def _next():
            for c in copies(1 - slot, grp + 1):
                c.start()

        for c in copies(slot, grp):
            c.wait()
        # every head against the whole rows of the group: the zero lanes of
        # q past latent + rope leave the pad (and nothing else) out
        s = jax.lax.dot_general(q_ref[0], buf[slot], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = col < n - grp * KB
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        # the values ARE the latents: the first dc lanes of the same tile
        acc = acc * corr + jax.lax.dot_general(
            p.astype(buf.dtype), buf[slot, :, :dc], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, ngrp, body, (jnp.full((H, 1), NEG_INF, jnp.float32),
                        jnp.zeros((H, 1), jnp.float32),
                        jnp.zeros((H, dc), jnp.float32)))
    o_ref[0] = (acc / jnp.where(l <= 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dc", "scale", "interpret"))
def _decode_pallas(q, pool, lengths, page_tbl, dc, scale, interpret):
    """q [B, H, W] (the absorbed query, the rotary query, zeros); pool
    [P, ps, W]; lengths [B]; page_tbl [B, M].  A jit of its own: a program
    that calls it once a layer lowers the kernel once."""
    B, H, W = q.shape
    ps = pool.shape[1]
    M = page_tbl.shape[1]
    kernel = functools.partial(_decode_kernel, ps=ps, M=M, dc=dc, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, dc), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, _GROUP * ps, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, _GROUP))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="latent_attention",
    )(lengths, page_tbl.reshape(-1), q, pool)


def _decode_dense(q, pool, lengths, page_tbl, dc, scale):
    """The same pass in plain XLA: gather every row's pages, then attend."""
    B, H, W = q.shape
    lat = pool[page_tbl].reshape(B, -1, W)                    # [B, L, W]
    s = _einsum_f32("bhw,blw->bhl", q, lat) * scale
    ok = (jnp.arange(lat.shape[1])[None, :] < lengths[:, None])[:, None, :]
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    return _einsum_f32("bhl,blc->bhc", p.astype(lat.dtype),
                       lat[..., :dc]).astype(q.dtype)


def kernel_ok(q, pool, dc):
    """The kernel's tiling: rows and values of whole lanes, pages of whole
    16-row tiles, the heads a whole sublane tile."""
    return (q.shape[-1] % LANES == 0 and dc % LANES == 0
            and pool.shape[1] % 16 == 0 and q.shape[1] % 8 == 0
            and pool.dtype == q.dtype)


def latent_decode_attention(q_abs, q_rope, pool, page_tbl, n, scale,
                            use_kernel=None, interpret=None):
    """One query a row, absorbed form.  q_abs [B, H, dc] (= W^K_h^T q^nope_h),
    q_rope [B, H, dr] (rotated); pool [P, ps, W]; page_tbl [B, M]; n int32
    [B] the context (the query sits at n - 1; 0 = an idle row: nothing is
    read, zeros come back).  Returns sum_j p_j c_j, [B, H, dc]."""
    B, H, dc = q_abs.shape
    W = pool.shape[-1]
    with jax.named_scope("latent_attention"):
        q = jnp.concatenate([q_abs, q_rope], axis=-1).astype(pool.dtype)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[-1])))
        if use_kernel is None:
            use_kernel = kernel_ok(q, pool, dc)
            _note("latent_kernel" if use_kernel else "latent_dense",
                  "tile_aligned" if use_kernel else "off_tile")
        n = n.astype(jnp.int32)
        if not use_kernel:
            return _decode_dense(q, pool, n, page_tbl, dc, float(scale))
        if interpret is None:
            interpret = _interpret_default()
        return _decode_pallas(q, pool, n, page_tbl.astype(jnp.int32), dc,
                              float(scale), interpret)


# ---------------------------------------------------------- prefill chunk
def pair_ops(H, dc, dr, d_nope, d_v):
    """Operations a (query, key) pair: (absorbed, expanded), and what
    expanding one context key costs."""
    return (2 * H * (dc + dr + dc), 2 * H * (d_nope + dr + d_v),
            2 * dc * H * (d_nope + d_v))


def expanded_wins(T, H, dc, dr, d_nope, d_v):
    """Whether T queries against one context are less work expanded: a key
    is expanded once whatever T, a pair is cheaper expanded."""
    absorbed, expanded, expand = pair_ops(H, dc, dr, d_nope, d_v)
    return T * expanded + expand < T * absorbed


def latent_chunk_attention(q_nope, q_rope, pool, page_row, off, w_kvb, dc,
                           scale, expanded=None):
    """A chunk of T queries of ONE sequence at positions off .. off + T - 1,
    after its latents were written.  q_nope [T, H, dn], q_rope [T, H, dr]
    (rotated); pool [P, ps, W]; page_row [1, M]; off int32 scalar; w_kvb
    [dc, H, dn + dv] (a head's W^K then W^V, transposed).  Returns
    [T, H, dv]."""
    T, H, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = w_kvb.shape[-1] - dn
    ps, W = pool.shape[1], pool.shape[2]
    if expanded is None:
        expanded = expanded_wins(T, H, dc, dr, dn, dv)
    with jax.named_scope("latent_chunk"):
        G = max(1, _CHUNK_KEYS // ps)
        KB = G * ps
        M = page_row.shape[-1]
        pages = jnp.pad(page_row.reshape(-1), (0, -M % G),
                        constant_values=TRASH_PAGE)
        qpos = off + jnp.arange(T, dtype=jnp.int32)
        dt = pool.dtype
        if expanded:
            qa, width = q_nope.astype(dt), dv
        else:
            with jax.named_scope("latent_absorb"):
                qa = _einsum_f32("thd,chd->thc", q_nope,
                                 w_kvb[..., :dn]).astype(dt)
            width = dc
        qr = q_rope.astype(dt)

        def body(i, carry):
            m, l, acc = carry
            lat = pool[jax.lax.dynamic_slice_in_dim(pages, i * G, G)]
            lat = lat.reshape(KB, W)
            c, kr = lat[:, :dc], lat[:, dc:dc + dr]
            if expanded:
                kv = _einsum_f32("kc,chd->khd", c, w_kvb).astype(dt)
                s = _einsum_f32("thd,khd->htk", qa, kv[..., :dn])
                vals = kv[..., dn:]                          # [KB, H, dv]
            else:
                s = _einsum_f32("thc,kc->htk", qa, c)
            s = (s + _einsum_f32("thr,kr->htk", qr, kr)) * scale
            ok = (i * KB + jnp.arange(KB, dtype=jnp.int32))[None, :] \
                <= qpos[:, None]                              # [T, KB]
            s = jnp.where(ok[None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
            p = jnp.where(ok[None], jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, -1, keepdims=True)
            pv = _einsum_f32("htk,khd->htd", p.astype(dt), vals) if expanded \
                else _einsum_f32("htk,kc->htc", p.astype(dt), c)
            return m_new, l, acc * corr + pv

        nblk = (off + T + KB - 1) // KB
        m, l, acc = jax.lax.fori_loop(
            0, nblk, body, (jnp.full((H, T, 1), NEG_INF, jnp.float32),
                            jnp.zeros((H, T, 1), jnp.float32),
                            jnp.zeros((H, T, width), jnp.float32)))
        out = acc / jnp.where(l <= 0.0, 1.0, l)               # [H, T, width]
        if not expanded:
            with jax.named_scope("latent_absorb"):
                out = _einsum_f32("htc,chd->htd", out.astype(dt),
                                  w_kvb[..., dn:])
        return jnp.transpose(out, (1, 0, 2)).astype(q_nope.dtype)
