"""Shared static + paged kv-cache layouts for the compiled decode loops.

Which layers keep a k/v cache at all is a model's ``cache_kinds()`` (one
``CacheKind`` a layer, below; ``SlotRows`` is what the layers with per-slot
state are handed, ``SparsePaged`` and ``LatentPaged`` what a block-sparse and
a latent attention layer are); a model without it keeps one in every layer.  The k/v
layouts themselves are four, distinguished by tuple length (see
generation.generate and inference/llm_server.py):
  (k_buf, v_buf, pos)                      — plain static, cache dtype = kv dtype
  (k_pages, v_pages, pos, page_tbl)        — PAGED plain: global page pool
                                             [P, H, page_size, D] + per-slot
                                             page tables [B, max_pages]
  (k_q, v_q, pos, k_scale, v_scale)        — int8 static + per-(head, token)
                                             absmax scales: HALF the HBM
                                             footprint AND half the decode
                                             stream when the Pallas decode
                                             kernel runs
                                             (ops/decode_attention.py
                                             dequantizes in VMEM)
  (k_pages, v_pages, pos, page_tbl,
   k_scale_pages, v_scale_pages)           — PAGED int8: scale pools are
                                             [P, H, page_size] f32

Paged layout contract (the vLLM/Ragged-Paged-Attention design, TPU-native):
  - page 0 is the TRASH page: never allocated to a slot; unused page-table
    entries point at it, so masked/padded scatters land there instead of in
    another slot's memory, and reads never see it (valid-length masking).
  - a token at absolute position t of slot b lives in page
    page_tbl[b, t // page_size] at row t % page_size; distinct live slots
    never share a page, so the vectorized scatter has no write collisions
    outside the trash page.
  - capacity is bounded by ACTUAL sequence lengths rounded up to a page,
    not by max_seq_len — the whole point: admission is by free pages.
  - SHARING (prefix cache, inference/prefix_cache.py): a page may appear in
    several slots' tables at once — requests with a common prompt prefix
    map the same physical pages and the host allocator refcounts them.
    Shared FULL pages are read-only by construction (every write lands at
    a position past the prompt); a shared partially-filled TAIL page is
    forked copy-on-write (``cow_copy_pages``) the moment a slot must write
    its continuation rows into it, so readers keep the frozen original.
    None of this reaches the kernel: it still just walks page tables.

Buffers are HEAD-MAJOR [B, H, L, D] (scales [B, H, L]): each (batch, head)
streams contiguous [L, D] keys/values — the layout the decode kernel and the
flash prefill kernel both want, with no per-step relayout.  New k/v arrive
from the projections as [B, S, H, D] and are transposed (cheap: S is 1 in
the decode loop) before the scatter at axis 2.

Both LlamaAttention and GPTBlock call the helpers here so the layout and
quantization contracts live in one place.

RECOMMENDATION: with the Pallas decode kernel the int8 cache streams half
the kv bytes (it dequantizes in VMEM) and by the same arithmetic nearly
doubles the max decode batch/context at fixed HBM (int8 payload + f32
per-token scales ~0.52x the bf16 bytes).  Its speed against bf16 has not
been measured on the attached chip (PERF.md; the earlier timings went with
their records in PR 21).  Default to cache_dtype="int8" for serving whenever
the model tolerates the ~absmax/254 per-element roundtrip error (logit drift
<5% on the parity test); keep bf16 for exact-parity evaluation runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dataclasses import dataclass
from typing import Any, NamedTuple

from ..tensor.tensor import apply_op


@dataclass(frozen=True)
class CacheKind:
    """What ONE layer keeps between the tokens of a sequence, as a model's
    ``cache_kinds()`` tells the serving engine (one entry a layer).  A model
    without that method keeps a pair of K/V page pools in every layer.

      "paged_kv"   page pools [pages, kv_heads, page_size, head_dim]; a
                   token's rows are found through its slot's page table.
                   With ``compressed`` = s > 0 the layer keeps a THIRD pool
                   [pages, kv_heads, page_size / s, head_dim] (the keys a
                   block selection scores, one every s tokens, each in the
                   page of its last token: ops/sparse_attention.py), takes a
                   ``SparsePaged`` and reports, per call, its query calls
                   and the context's and the selected blocks ([3] int32)
      "recurrent"  fixed-size state a SLOT, not a page: ``state`` lists
                   (name, shape of one slot's, dtype)
      "paged_latent"  ONE page pool [pages, page_size, width] of a
                   multi-head latent attention layer: a token's row holds its
                   compressed latent (``latent_dim`` values, from which every
                   head's keys AND values are projections) and the one rotary
                   key all heads share (``rope_dim``), padded to whole lanes
                   (ops/latent_attention.py: ``pool_width``).  No V pool.
                   The layer takes a ``LatentPaged`` and reports, per call,
                   its real queries and their contexts' tokens ([2] int32);
                   the same pages, table, refcounts, prefix cache and COW
                   fork as "paged_kv".  int8 pages, the KV tiers and
                   speculation are refused for it (LLMEngine)
      "none"       nothing (a feed-forward or expert layer)

    A layer of any kind whose feed-forward is routed experts sets
    ``experts_held`` > 0 (and ``top_k``): it also reports, per call, its
    pairs by held expert and the experts touched ([experts_held + 1] int32).
    """
    kind: str
    kv_heads: int = 0
    head_dim: int = 0
    state: tuple = ()
    experts_held: int = 0
    top_k: int = 0
    compressed: int = 0
    latent_dim: int = 0
    rope_dim: int = 0


class SlotRows(NamedTuple):
    """The rows of a batch against the engine's slots, handed to the layers
    whose state is a slot's (and to expert layers, which count real rows)."""
    rows: Any     # int32 [b]: the slot behind each row; None = row i is slot i
    fresh: Any    # bool [b]: the row opens a sequence, its state starts at
                  # zero; None = no row does
    n_valid: Any  # int32 [b]: leading tokens of the row that are real; a row
                  # with 0 leaves its slot's state exactly as it was.  The
                  # engine's decode program has no other word for "this row
                  # is idle": it reads 0 where the tick masked the row's page
                  # table to the trash page (page_tbl[:, 0] == 0), 1 elsewhere
    pos: Any = None  # int32 [b]: the position of each row's first token (a
                     # state layer that rotates by position has no page
                     # tuple to read it from)


class SparsePaged(NamedTuple):
    """What a "paged_kv" layer with ``compressed`` is handed: its three
    pools, where the batch's rows stand, and the rows themselves."""
    k: Any         # [pages, kv_heads, page_size, head_dim]
    v: Any
    ck: Any        # [pages, kv_heads, page_size / compressed, head_dim]
    pos: Any       # int32 [b]
    page_tbl: Any  # int32 [b, max_pages]
    rows: Any      # SlotRows


class LatentPaged(NamedTuple):
    """What a "paged_latent" layer is handed: its one pool, where the batch's
    rows stand, and the rows themselves."""
    pool: Any      # [pages, page_size, width]
    pos: Any       # int32 [b] (or a scalar)
    page_tbl: Any  # int32 [b, max_pages]
    rows: Any      # SlotRows


def _quantize_kv(kv):
    """Per-(head, token) absmax int8 quantization of a HEAD-MAJOR
    [B, H, S, D] slice: returns (int8 values, f32 scale [B, H, S])."""
    f = kv.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _to_head_major(kv):
    """[B, S, H, D] (projection layout) -> [B, H, S, D] (cache layout)."""
    return jnp.transpose(kv, (0, 2, 1, 3))


def _scatter(buf, kv, offset):
    """Write head-major new kv into the buffer at `offset` — a scalar (all
    slots aligned: the generate() loop) or a per-slot [B] vector
    (continuous batching decode S == 1; speculative verify S == K+1, where
    token t of slot b lands at row offset[b] + t and rows past the
    buffer's extent are dropped by the scatter's out-of-bounds rule)."""
    hm = kv
    if getattr(offset, "ndim", 0) >= 1:
        B, H = buf.shape[0], buf.shape[1]
        S = hm.shape[2]
        bi = jnp.arange(B)[:, None, None]
        hi = jnp.arange(H)[None, :, None]
        ti = offset[:, None, None] + jnp.arange(S, dtype=jnp.int32)[None, None, :]
        return buf.at[bi, hi, ti].set(hm)
    return jax.lax.dynamic_update_slice_in_dim(buf, hm, offset, 2)


def update_plain_cache(cache, k, v, offset):
    """Scatter new k/v [B, S, H, D] into the head-major (k_buf, v_buf, pos)
    layout.  Returns (new_cache, k_full, v_full) with the full buffers in
    head-major [B, H, L, D]."""
    S = k.shape[1]
    upd = lambda buf, kv: _scatter(  # noqa: E731
        buf, _to_head_major(kv.astype(buf.dtype)), offset)
    k_buf = apply_op(upd, (cache[0], k), name="kv_scatter")
    v_buf = apply_op(upd, (cache[1], v), name="kv_scatter")
    return (k_buf, v_buf, offset + S), k_buf, v_buf


def update_quant_cache(cache, k, v, offset, out_dtype):
    """Quantize + scatter new k/v [B, S, H, D] into the head-major 5-tuple
    int8 layout.  Returns (new_cache, k_q, v_q, k_scale, v_scale) — the
    int8 buffers and scales go STRAIGHT to the decode kernel, which
    dequantizes in VMEM (no bf16 cache materialization in HBM)."""
    S = k.shape[1]

    def upd_q(buf, sbuf, kv):
        kv_q, scale = _quantize_kv(_to_head_major(kv))
        if getattr(offset, "ndim", 0) >= 1:
            B, H = buf.shape[0], buf.shape[1]
            Sq = kv_q.shape[2]
            bi = jnp.arange(B)[:, None, None]
            hi = jnp.arange(H)[None, :, None]
            ti = offset[:, None, None] \
                + jnp.arange(Sq, dtype=jnp.int32)[None, None, :]
            return (buf.at[bi, hi, ti].set(kv_q),
                    sbuf.at[bi, hi, ti].set(scale))
        return (jax.lax.dynamic_update_slice_in_dim(buf, kv_q, offset, 2),
                jax.lax.dynamic_update_slice_in_dim(sbuf, scale, offset, 2))

    k_buf, k_sc = apply_op(upd_q, (cache[0], cache[3], k), name="kv_scatter_q")
    v_buf, v_sc = apply_op(upd_q, (cache[1], cache[4], v), name="kv_scatter_q")
    return (k_buf, v_buf, offset + S, k_sc, v_sc), k_buf, v_buf, k_sc, v_sc


# ------------------------------------------------------------------- paged

TRASH_PAGE = 0  # reserved pool slot: padding/garbage writes land here


def pages_for(n_tokens, page_size):
    """Pages needed to hold n_tokens (host-side allocator arithmetic)."""
    return -(-int(n_tokens) // int(page_size))


def cow_copy_pages(caches, src, dst):
    """Copy page ``src``'s rows into page ``dst`` across every layer's
    pools — the device side of a COPY-ON-WRITE fork.  ``caches`` is the
    engine's per-layer list of pool tuples (k/v pools, plus scale pools in
    the int8 layout, or a latent layer's one pool — every element is
    ``[P, ...]`` page-major, so one generic row copy covers them all).  The caller then repoints the
    writing slot's page-table entry at ``dst``; readers of ``src`` are
    untouched."""
    with jax.named_scope("cow_copy"):
        return [tuple(x.at[dst].set(x[src]) for x in c) for c in caches]


def gather_pages_to_host(caches, pages):
    """Gather the rows of page ids ``pages`` ([N] int32) across every
    layer's pools in ONE batched program — the device half of a DEMOTION
    (hierarchical kv: HBM -> host RAM).  ``caches`` is the engine's
    per-layer list of pool tuples (k/v pools plus scale pools in the int8
    layout; every element is ``[P, ...]`` page-major, the same contract as
    :func:`cow_copy_pages`), so one generic row gather covers both
    layouts.  Returns per-layer tuples of ``[N, ...]`` blocks; the caller
    fetches them host-side (``np.asarray``) OUTSIDE any engine lock —
    dispatch is async, the transfer is the blocking part."""
    with jax.named_scope("kv_tier_gather"):
        return [tuple(x[pages] for x in c) for c in caches]


def upload_host_pages(caches, pages, blocks):
    """Scatter host-staged page blocks back into the pools in ONE batched
    program — the device half of a PROMOTION (host RAM -> HBM), the dual
    of :func:`gather_pages_to_host`.  ``blocks`` mirrors the gather's
    output: per-layer tuples of ``[N, ...]`` rows, scattered to page ids
    ``pages`` ([N] int32).  Padding entries may target ``TRASH_PAGE``
    (garbage rows land in the reserved page, never in live memory).  The
    caller typically donates ``caches`` — after the upload the promoted
    pages are indistinguishable from never-evicted ones (the ragged paged
    kernel just walks page tables)."""
    with jax.named_scope("kv_tier_upload"):
        return [tuple(x.at[pages].set(b) for x, b in zip(c, blk))
                for c, blk in zip(caches, blocks)]


def _token_pages_rows(pos, page_tbl, S, page_size, max_pages):
    """Per-token (page id, row) for S new tokens starting at `pos` (scalar
    or [B]).  Positions past the table's coverage (a padded prefill tail
    overflowing max_pages * page_size) route to TRASH_PAGE explicitly — a
    clip to the last entry would alias a fully-populated table's REAL last
    page and clobber live rows.  Within coverage, unallocated entries
    already point at TRASH_PAGE by the engine's convention."""
    B = page_tbl.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    tpos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B, S]
    in_table = tpos < max_pages * page_size
    pidx = jnp.clip(tpos // page_size, 0, max_pages - 1)
    page = jnp.take_along_axis(page_tbl, pidx, axis=1)             # [B, S]
    page = jnp.where(in_table, page, TRASH_PAGE)
    return page, tpos % page_size


def _scatter_rows(pool, vals, page, row):
    """Write vals [B, S, H, ...] into pool [P, H, page_size, ...] at
    (page, row) [B, S]: one token's heads go to one row of one page."""
    hi = jnp.arange(pool.shape[1])[None, None, :]
    return pool.at[page[..., None], hi, row[..., None]].set(vals)


def _chunk_pages(off, page_row, C, page_size):
    """Where ONE slot's run of C tokens from position ``off`` ([1] or
    scalar) lies, through its page-table row ``page_row`` [1, M]: the ids of
    the n pages it can touch ([n]; TRASH_PAGE past the table's coverage, as
    ``_token_pages_rows`` routes them), the run's offset in the first, and
    which rows of each page are the run's ([n, page_size] bool)."""
    n, M = -(-C // page_size) + 1, page_row.shape[1]
    off = jnp.asarray(off, jnp.int32).reshape(())
    shift = off % page_size
    idx = off // page_size + jnp.arange(n, dtype=jnp.int32)
    pages = jnp.where(idx < M, page_row[0, jnp.minimum(idx, M - 1)],
                      TRASH_PAGE)
    i = jnp.arange(n * page_size, dtype=jnp.int32).reshape(n, page_size) - shift
    return pages, shift, (i >= 0) & (i < C)


def _write_chunk_pages(pool, vals, at):
    """Write the run vals [C, H, ...] into pool [P, H, page_size, ...] at
    ``at`` (``_chunk_pages``), a whole page at a time: each page the run can
    touch is read, its rows of the run replaced, and written back with one
    dynamic-update-slice — the rows ``_scatter_rows`` writes at
    ``_token_pages_rows``' (page, row).  A scatter writes its updates a row
    at a time (~70 ns a 128-lane row on a TPU v5e: 2,304 rows a layer and
    pool for a chunk of 256 tokens at 8 K/V heads); a page block goes whole."""
    pages, shift, mine = at
    (n, ps), C = mine.shape, vals.shape[0]
    padded = jnp.pad(vals, [(ps, n * ps - C)] + [(0, 0)] * (vals.ndim - 1))
    rows = jax.lax.dynamic_slice_in_dim(padded, ps - shift, n * ps, 0)
    blocks = jnp.swapaxes(rows.reshape((n, ps) + vals.shape[1:]), 1, 2)
    mine = mine.reshape((n, 1, ps) + (1,) * (vals.ndim - 2))
    for j in range(n):
        old = jax.lax.dynamic_index_in_dim(pool, pages[j], 0, keepdims=False)
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, jnp.where(mine[j], blocks[j], old), pages[j], 0)
    return pool


def _paged_scatter(pool, hm, pos, page_tbl):
    """Write head-major new kv [B, H, S, D] into the page pool
    [P, H, page_size, D] at absolute positions pos..pos+S-1 of each slot,
    routed through that slot's page-table row."""
    page, row = _token_pages_rows(pos, page_tbl, hm.shape[2], pool.shape[2],
                                  page_tbl.shape[1])
    return _scatter_rows(pool, jnp.transpose(hm, (0, 2, 1, 3)), page, row)


def _paged_scatter_scale(spool, scale, pos, page_tbl):
    """Same routing for the f32 scale pool [P, H, page_size]; scale arrives
    head-major [B, H, S]."""
    page, row = _token_pages_rows(pos, page_tbl, scale.shape[2],
                                  spool.shape[2], page_tbl.shape[1])
    return _scatter_rows(spool, jnp.transpose(scale, (0, 2, 1)), page, row)


def _pool_rows(kv, pool):
    """New kv [..., H, D] as the pool's rows: a pool whose rows are wider
    than a head holds W / D heads side by side on each
    (ops/decode_attention.py: ``pool_layout``); otherwise kv as it is."""
    W = pool.shape[-1]
    if kv.shape[-1] == W:
        return kv
    return kv.reshape(kv.shape[:-2] + (kv.shape[-2] * kv.shape[-1] // W, W))


def update_paged_cache(cache, k, v, offset):
    """Scatter new k/v [B, S, H, D] into the paged 4-tuple layout.  Returns
    (new_cache, k_pages, v_pages) — the pools plus the (unchanged) page
    table go straight to paged_decode_attention."""
    S = k.shape[1]
    upd = lambda pool, kv, tbl: _paged_scatter(  # noqa: E731
        pool, _to_head_major(_pool_rows(kv, pool).astype(pool.dtype)),
        offset, tbl)
    with jax.named_scope("kv_write"):
        k_pool = apply_op(upd, (cache[0], k, cache[3]), name="kv_paged_scatter")
        v_pool = apply_op(upd, (cache[1], v, cache[3]), name="kv_paged_scatter")
    return (k_pool, v_pool, offset + S, cache[3]), k_pool, v_pool


def update_paged_quant_cache(cache, k, v, offset):
    """Quantize + scatter new k/v [B, S, H, D] into the paged int8 6-tuple.
    Returns (new_cache, k_pages, v_pages, k_scale_pages, v_scale_pages)."""
    S = k.shape[1]

    def upd_q(pool, spool, kv, tbl):
        kv_q, scale = _quantize_kv(_to_head_major(kv))
        return (_paged_scatter(pool, kv_q, offset, tbl),
                _paged_scatter_scale(spool, scale, offset, tbl))

    with jax.named_scope("kv_write"):
        k_pool, k_sc = apply_op(upd_q, (cache[0], cache[4], k, cache[3]),
                                name="kv_paged_scatter_q")
        v_pool, v_sc = apply_op(upd_q, (cache[1], cache[5], v, cache[3]),
                                name="kv_paged_scatter_q")
    return ((k_pool, v_pool, offset + S, cache[3], k_sc, v_sc),
            k_pool, v_pool, k_sc, v_sc)


def paged_attention_update(cache, q, k, v, offset, scale=None):
    """Scatter new k/v [B, S, H, D] into the paged cache, then attend q
    through the page table (the ragged paged Pallas kernel for ANY S >= 1
    on tile-aligned shapes — decode, prefill chunks, the K+1 spec-verify
    ladder; gathered dense math only for CPU-odd shapes) — the ONE paged
    decode / chunked-prefill / verify hot path shared by every attention
    family that understands the paged 4/6-tuples.  ``scale`` is the
    softmax's (1/sqrt(D) when None).  Returns (new_cache, out [B, S, Hq, D])."""
    from ..ops.decode_attention import paged_decode_attention

    if len(cache) == 6:
        if k.shape[-1] != cache[0].shape[-1]:
            raise ValueError(
                "cache_dtype='int8' keeps a scale a head and token, and a "
                f"pool of {cache[0].shape[-1]}-wide rows holds several "
                f"{k.shape[-1]}-wide heads a row")
        new_cache, k_q, v_q, k_sc, v_sc = update_paged_quant_cache(
            cache, k, v, offset)
        out = apply_op(
            lambda qq, kk, vv, pt, ks, vs: paged_decode_attention(
                qq, kk, vv, offset, pt, ks, vs, scale=scale),
            (q, k_q, v_q, cache[3], k_sc, v_sc),
            name="paged_decode_attention")
    else:
        new_cache, k_p, v_p = update_paged_cache(cache, k, v, offset)
        out = apply_op(
            lambda qq, kk, vv, pt: paged_decode_attention(
                qq, kk, vv, offset, pt, scale=scale),
            (q, k_p, v_p, cache[3]), name="paged_decode_attention")
    return new_cache, out


def paged_mixed_update(cache, chunk, q, k, v, scale=None):
    """A tick's two kinds of row through ONE layer's pools: q, k, v
    [1, C + B, H, D] hold a prefill chunk's C rows — one slot's, at
    ``chunk`` = (off [1], page_row [1, M]) — and behind them B decode rows,
    one a slot, at ``cache``'s own pos [B] and page table [B, M].  The
    chunk's k/v go into their pages a page block at a time
    (``_write_chunk_pages``), the decode rows' by one scatter a pool; each
    kind then attends through its own table, the same kernel called twice.  The
    chunk's slot decodes nothing in the same program (the engine masks its
    table row to the trash page), no page that others read is ever written,
    so the two kinds' writes never meet and neither read sees the other's.
    ``scale`` as in ``paged_attention_update``.  Returns (new_cache in
    ``cache``'s form, out [1, C + B, Hq, D])."""
    from ..ops.decode_attention import paged_decode_attention

    off, page_row = chunk
    pos = cache[2]
    C = q.shape[1] - pos.shape[0]

    at = _chunk_pages(off, page_row, C, cache[0].shape[2])

    def write(pool, rows, tbl):
        # the chunk's rows a page block at a time, then one scatter of the
        # decode rows, one a slot
        pool = _write_chunk_pages(pool, rows[0, :C], at)
        page, row = _token_pages_rows(pos, tbl, 1, pool.shape[2], tbl.shape[1])
        return _scatter_rows(pool, rows[0, C:, None], page, row)

    def attend(qq, kk, vv, tbl, *scales):
        oc = paged_decode_attention(qq[:, :C], kk, vv, off, page_row, *scales,
                                    scale=scale)
        od = paged_decode_attention(qq[0, C:, None], kk, vv, pos, tbl, *scales,
                                    scale=scale)
        return jnp.concatenate([oc, od[None, :, 0]], axis=1)

    if len(cache) == 6:
        def upd_q(pool, spool, kv, tbl):
            kv_q, scale = _quantize_kv(_to_head_major(kv))
            return (write(pool, jnp.transpose(kv_q, (0, 2, 1, 3)), tbl),
                    write(spool, jnp.transpose(scale, (0, 2, 1)), tbl))

        with jax.named_scope("kv_write"):
            k_pool, k_sc = apply_op(upd_q, (cache[0], cache[4], k, cache[3]),
                                    name="kv_paged_scatter_q")
            v_pool, v_sc = apply_op(upd_q, (cache[1], cache[5], v, cache[3]),
                                    name="kv_paged_scatter_q")
        out = apply_op(attend, (q, k_pool, v_pool, cache[3], k_sc, v_sc),
                       name="paged_decode_attention")
        return (k_pool, v_pool, pos + 1, cache[3], k_sc, v_sc), out
    upd = lambda pool, kv, tbl: write(  # noqa: E731
        pool, _pool_rows(kv, pool).astype(pool.dtype), tbl)
    with jax.named_scope("kv_write"):
        k_pool = apply_op(upd, (cache[0], k, cache[3]), name="kv_paged_scatter")
        v_pool = apply_op(upd, (cache[1], v, cache[3]), name="kv_paged_scatter")
    out = apply_op(attend, (q, k_pool, v_pool, cache[3]),
                   name="paged_decode_attention")
    return (k_pool, v_pool, pos + 1, cache[3]), out
