"""Block-sparse attention over the page pool: a learned selection of key
blocks inside paged attention (the MiniCPM4 / InfLLM-v2 family's rule).

A layer keeps, beside its K and V page pools, a third and small one: the
COMPRESSED KEYS the selection scores,

    C_j = mean(k_i, i in [stride j, stride j + kernel_size))

one every `kernel_stride` tokens (1/16 of K at the published sizes).  C_j
lives IN THE PAGE THAT HOLDS ITS LAST TOKEN, in row ((stride j + kernel_size
- 1) mod page_size) // stride, so a page's content depends only on the chain
of tokens up to its end and a shared page stays shareable (prefix cache).
Gathered through a slot's page table the pool reads as one array whose row i
holds C_{i - shift}, shift = kernel_size / stride - 1; row 0 is never written.

For the query at position t (context n = t + 1) and a K/V head g with its
`rep` query heads:

    p_h = softmax_j(q_h . C_j / sqrt(D))   over the j with stride j + kernel_size <= n
    s_j = sum_{h in g} p_{h, j}
    b_m = max(s_j over the C_j that overlap block m = tokens [block m, block (m + 1)))
    block 0 .. init_blocks - 1 and every block that overlaps the last
    `window_size` tokens: +inf
    selected = the `topk` best blocks (ties to the lower index), or every
    block where n <= dense_len

and attention runs over the tokens i <= t of the selected blocks alone.  The
rule is read PER QUERY POSITION, so chunked prefill, decode through the cache
and one forward pass over the whole sequence define the same function.

``select_blocks`` is the selection (named scope ``sparse_select``, which
``gather_compressed`` shares: plain XLA over the gathered compressed keys; the
order of the blocks by counting, no sort).
``sparse_paged_attention`` is the decode pass: a Pallas kernel that reads only
the selected blocks' rows of the K and V pools, one (slot, K/V head) a grid
step, the blocks fetched by explicit copies in groups of ``_GROUP`` (double
buffered) and folded into an online soft-max; the gathered XLA pass serves
the CPU and shapes off the tile (chosen by the shape, as `kernel_ok` says).
``sparse_chunk_attention`` is a prefill chunk: every query of the chunk
selects for itself, and the chunk attends the slot's gathered context under
the selections' mask, in sub-blocks of queries.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._prng import interpret_default as _interpret_default

NEG_INF = -1e30
TRASH_PAGE = 0   # models.kv_cache.TRASH_PAGE: never allocated, garbage lands there
_GROUP = 8       # blocks fetched and scored together by the kernel
_Q_SUB = 32      # queries of a chunk scored at once (bounds the score matrix)


@dataclass(frozen=True)
class SparseSpec:
    """The selection's sizes (`sparse_config` of the family)."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size % self.kernel_stride \
                or self.block_size % self.kernel_stride:
            raise ValueError("kernel_size and block_size must be multiples of "
                             "kernel_stride")

    @property
    def shift(self):
        """Gathered row i of the compressed pool holds C_{i - shift}."""
        return self.kernel_size // self.kernel_stride - 1

    @property
    def list_len(self):
        """Entries a selected-block list needs: `topk`, or every block of a
        context still read whole."""
        return max(self.topk, -(-self.dense_len // self.block_size))

    def selected(self, n):
        """How many blocks the rule reads at context n (host arithmetic)."""
        blocks = -(-int(n) // self.block_size)
        return blocks if n <= self.dense_len else min(self.topk, blocks)


def _einsum_f32(eq, a, b):
    """einsum with a float32 result.  On the TPU the operands stay as they
    are (the MXU accumulates bfloat16 products in float32); the CPU backend
    has no bfloat16 x bfloat16 = float32 product for every contraction, so
    there the operands are widened first."""
    if _interpret_default():
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


# ------------------------------------------------------- compressed keys
def write_compressed(ck_pool, k_pool, pos, page_tbl, n_new, spec):
    """Write the compressed keys completed by the `n_new` tokens just
    scattered into `k_pool` at positions pos .. pos + n_new - 1 of each row.
    ck_pool [P, H, page_size / stride, D], k_pool [P, H, page_size, D], pos
    int32 [B], page_tbl [B, M].  A window that ends past what a row really
    holds (a padded tail, an idle row's trash page) writes garbage where the
    next real token will overwrite it, or into the trash page; the selection
    never reads a C_j whose window ends past the context."""
    P, H, ps, D = k_pool.shape
    st, ks = spec.kernel_stride, spec.kernel_size
    if ps % st:
        raise ValueError(f"page_size {ps} is not whole strides of {st}")
    B, M = page_tbl.shape
    gp = ps // st                      # granules (and compressed rows) a page
    nc = -(-n_new // st)               # windows a run of n_new tokens can end
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    i = pos[:, None] // st + jnp.arange(nc, dtype=jnp.int32)[None, :]   # [B, nc]
    last = i * st + st - 1             # the window's last token
    # last >= pos always: the first candidate is the window that ends at or
    # after the first new token
    ok = (last < pos[:, None] + n_new) & (last + 1 >= ks) & (last < M * ps)
    # the window = granules i - shift .. i of the slot, each inside one page
    g = i[:, :, None] - jnp.arange(spec.shift, -1, -1, dtype=jnp.int32)  # [B,nc,R]
    g = jnp.clip(g, 0, M * gp - 1)
    page = jnp.take_along_axis(page_tbl, (g // gp).reshape(B, -1), axis=1)
    rows = k_pool.reshape(P, H, gp, st, D)[page, :, (g % gp).reshape(B, -1)]
    rows = rows.reshape(B, nc, spec.shift + 1, H, st, D).astype(jnp.float32)
    ck = jnp.mean(rows, axis=(2, 4)).astype(ck_pool.dtype)              # [B,nc,H,D]
    dst = jnp.take_along_axis(page_tbl, jnp.clip(last // ps, 0, M - 1), axis=1)
    dst = jnp.where(ok, dst, TRASH_PAGE)
    return ck_pool.at[dst[..., None], jnp.arange(H)[None, None, :],
                      ((last % ps) // st)[..., None]].set(ck)


def gather_compressed(ck_pool, page_tbl):
    """[P, H, gp, D] through [B, M] -> [B, H, M * gp, D]: row i is C_{i - shift}.
    Part of the selection's cost (only it reads the gathered keys), so under
    its scope."""
    with jax.named_scope("sparse_select"):
        g = ck_pool[page_tbl]          # [B, M, H, gp, D]
        B, M, H, gp, D = g.shape
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(B, H, M * gp, D)


# ------------------------------------------------------------- selection
def block_scores(q, ck, n, spec):
    """q [B, T, H, rep, D] (T query positions a row), ck [B, H, I, D] the
    row's gathered compressed keys, n int32 [B, T] each query's context.
    Returns float32 [B, T, H, I stride / block]: b_m, +inf where the rule
    forces the block (or reads every block), -inf past the context."""
    B, T, H, rep, D = q.shape
    I = ck.shape[2]
    st, ks, blk = spec.kernel_stride, spec.kernel_size, spec.block_size
    ratio = blk // st
    nb = I // ratio
    logits = _einsum_f32("bthrd,bhid->bthri", q, ck) / D ** 0.5
    j = jnp.arange(I, dtype=jnp.int32) - spec.shift
    valid = (j >= 0) & (j * st + ks <= n[..., None])                    # [B,T,I]
    v5 = valid[:, :, None, None, :]
    logits = jnp.where(v5, logits, NEG_INF)
    p = jnp.where(v5, jnp.exp(logits - jnp.max(logits, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    s = jnp.sum(p, axis=3)                                              # [B,T,H,I]
    # block m is overlapped by rows m ratio .. m ratio + ratio + shift - 1
    b = jnp.max(s.reshape(B, T, H, nb, ratio), axis=-1)
    sp = jnp.pad(s, ((0, 0),) * 3 + ((0, ratio + spec.shift),))
    for d in range(spec.shift):
        b = jnp.maximum(b, sp[..., ratio + d::ratio][..., :nb])
    m = jnp.arange(nb, dtype=jnp.int32)
    nn = n[:, :, None, None]
    forced = (m < spec.init_blocks) | ((m + 1) * blk > nn - spec.window_size) \
        | (nn <= spec.dense_len)
    return jnp.where(m * blk < nn, jnp.where(forced, jnp.inf, b), -jnp.inf)


def select_blocks(q, ck, n, spec):
    """The selected blocks of every (row, query position, K/V head).
    Returns (idx int32 [B, T, H, K] best first — forced blocks, then by score,
    ties to the lower index; entries from `cnt` on are not selected —, cnt
    int32 [B, T] the entries a reader takes, picked int32 [B, T]), K =
    min(spec.list_len, blocks the table can hold).  `picked` is read back
    from the LIST, not from the context's length: the distinct blocks inside
    the context among a head's first `cnt` entries, over the K/V heads (their
    sum, floor-divided by H).  It equals `cnt` while the order is a
    permutation of the blocks with the context's first; a rank that repeats,
    a NaN score or an index off the context shows as another count
    (`stats()["sparse_attention"]` counts it)."""
    with jax.named_scope("sparse_select"):
        scores = block_scores(q, ck, n, spec)
        nb = scores.shape[-1]
        k = min(spec.list_len, nb)
        # a block's place in the order "best first, ties to the lower index"
        # is the number of blocks that come before it: nb^2 comparisons a
        # (row, K/V head), which fuse into one reduction, where a sort of the
        # nb scores took 6% of the device's time (PERF.md, PR 31)
        m = jnp.arange(nb, dtype=jnp.int32)
        mine, other = scores[..., :, None], scores[..., None, :]
        rank = jnp.sum((other > mine) | ((other == mine) & (m[None, :] < m[:, None])),
                       axis=-1, dtype=jnp.int32)                       # [B,T,H,nb]
        idx = jnp.sum(jnp.where(rank[..., None, :] == m[:k, None], m, 0),
                      axis=-1, dtype=jnp.int32)                        # [B,T,H,k]
        blocks = -(-n // spec.block_size)
        cnt = jnp.where(n <= spec.dense_len, blocks,
                        jnp.minimum(spec.topk, blocks))
        cnt = jnp.minimum(cnt, k).astype(jnp.int32)
        listed = jnp.any((idx[..., :, None] == m)
                         & (m[:k, None] < cnt[..., None, None, None]), axis=-2)
        picked = jnp.sum(listed & (m < blocks[..., None, None]),
                         axis=(-2, -1), dtype=jnp.int32) // scores.shape[2]
        return idx, cnt, picked


# ----------------------------------------------------------- decode pass
def _sparse_kernel(phys_ref, cnt_ref, last_ref, rem_ref, q_ref, k_hbm, v_hbm,
                   o_ref, kbuf, vbuf, sem, *, blk, bpp, scale):
    """One (slot, K/V head): the head's `rep` queries against the selected
    blocks, fetched `_GROUP` at a time into one of two buffers while the
    other is scored.  phys[b, g, e] = page * bpp + block inside the page."""
    b, g = pl.program_id(0), pl.program_id(1)
    cnt = cnt_ref[b]
    ngrp = (cnt + _GROUP - 1) // _GROUP
    rep, D = q_ref.shape[-2], q_ref.shape[-1]
    W = _GROUP * blk

    def copies(slot, grp):
        out = []
        for e in range(_GROUP):
            a = phys_ref[b, g, grp * _GROUP + e]
            src = (a // bpp, g, pl.ds((a % bpp) * blk, blk))
            dst = (slot, pl.ds(e * blk, blk))
            out.append(pltpu.make_async_copy(k_hbm.at[src], kbuf.at[dst],
                                             sem.at[0, slot]))
            out.append(pltpu.make_async_copy(v_hbm.at[src], vbuf.at[dst],
                                             sem.at[1, slot]))
        return out

    @pl.when(ngrp > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    col = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def body(grp, carry):
        m, l, acc = carry
        slot = grp % 2

        @pl.when(grp + 1 < ngrp)
        def _next():
            for c in copies(1 - slot, grp + 1):
                c.start()

        for c in copies(slot, grp):
            c.wait()
        # how many leading tokens of each fetched block the query may read:
        # the block that holds the query itself is cut at it, an entry past
        # `cnt` (the wrapper points it at the trash page) reads none
        lim = jnp.zeros((1, W), jnp.int32)
        for e in range(_GROUP):
            ent = grp * _GROUP + e
            a = phys_ref[b, g, ent]
            n_e = jnp.where(ent < cnt,
                            jnp.where(a == last_ref[b], rem_ref[b], blk), 0)
            lim = jnp.where(col // blk == e, n_e, lim)
        q = q_ref[0, 0]
        s = jax.lax.dot_general(q, kbuf[slot], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = col % blk < lim
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, ngrp, body, (jnp.full((rep, 1), NEG_INF, jnp.float32),
                        jnp.zeros((rep, 1), jnp.float32),
                        jnp.zeros((rep, D), jnp.float32)))
    o_ref[0, 0] = (acc / jnp.where(l <= 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _sparse_pallas(q, k_pool, v_pool, phys, cnt, last, rem, blk, interpret):
    """q [B, H, rep, D]; pools [P, H, ps, D]; phys [B, H, K] (K whole groups);
    cnt, last, rem [B].  A jit of its own: a program that calls it once a
    layer lowers the kernel once."""
    B, H, rep, D = q.shape
    ps = k_pool.shape[2]
    kernel = functools.partial(_sparse_kernel, blk=blk, bpp=ps // blk,
                               scale=1.0 / D ** 0.5)
    qspec = pl.BlockSpec((1, 1, rep, D), lambda b, g, *_: (b, g, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, H),
            in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((2, _GROUP * blk, D), k_pool.dtype),
                            pltpu.VMEM((2, _GROUP * blk, D), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="sparse_paged_attention",
    )(phys, cnt, last, rem, q, k_pool, v_pool)


def _sparse_dense(q, k_pool, v_pool, phys, cnt, last, rem, blk):
    """The same pass in plain XLA: gather the selected blocks, then attend."""
    B, H, rep, D = q.shape
    P, _, ps, _ = k_pool.shape
    bpp = ps // blk
    K = phys.shape[-1]
    hi = jnp.arange(H)[None, :, None]

    def blocks(pool):
        return pool.reshape(P, H, bpp, blk, D)[phys // bpp, hi, phys % bpp]

    k, v = blocks(k_pool), blocks(v_pool)                   # [B, H, K, blk, D]
    s = _einsum_f32("bhrd,bhkid->bhrki", q, k) / D ** 0.5
    lim = jnp.where(jnp.arange(K)[None, None, :] < cnt[:, None, None],
                    jnp.where(phys == last[:, None, None],
                              rem[:, None, None], blk), 0)  # [B, H, K]
    ok = (jnp.arange(blk)[None, None, None, :] < lim[..., None])[:, :, None]
    s = jnp.where(ok, s, NEG_INF).reshape(B, H, rep, K * blk)
    p = jnp.where(ok.reshape(B, H, 1, K * blk),
                  jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    return _einsum_f32("bhrk,bhkd->bhrd", p.astype(v.dtype),
                       v.reshape(B, H, K * blk, D)).astype(q.dtype)


def kernel_ok(q, k_pool, blk):
    """The kernel's tiling: 128-lane rows, blocks of whole 16-row tiles that
    divide the page, query heads a whole sublane tile."""
    rep, D = q.shape[-2], q.shape[-1]
    return (D % 128 == 0 and blk % 16 == 0 and k_pool.shape[2] % blk == 0
            and rep % 8 == 0 and k_pool.dtype == q.dtype)


def sparse_paged_attention(q, k_pool, v_pool, page_tbl, idx, cnt, n, spec,
                           use_kernel=None, interpret=None):
    """One query a row against its selected blocks.  q [B, Hq, D]; pools
    [P, H, page_size, D]; page_tbl [B, M]; idx int32 [B, H, K] and cnt [B]
    from `select_blocks` (cnt 0 = an idle row: nothing is read, zeros come
    back); n int32 [B] the context (the query sits at n - 1).  Returns
    [B, Hq, D]."""
    B, Hq, D = q.shape
    H, ps = k_pool.shape[1], k_pool.shape[2]
    blk = spec.block_size
    bpp = ps // blk
    with jax.named_scope("sparse_paged_attention"):
        K = -(-idx.shape[-1] // _GROUP) * _GROUP
        idx = jnp.pad(idx, ((0, 0), (0, 0), (0, K - idx.shape[-1])))
        page = jnp.take_along_axis(
            page_tbl, jnp.clip(idx // bpp, 0, page_tbl.shape[1] - 1).reshape(B, -1),
            axis=1).reshape(B, H, K)
        live = jnp.arange(K)[None, None, :] < cnt[:, None, None]
        # an entry past cnt points at the trash page: fetched whole groups
        # hold finite rows, and the mask drops them
        phys = jnp.where(live, page * bpp + idx % bpp, TRASH_PAGE * bpp)
        tail = jnp.maximum(n - 1, 0) // blk
        last = jnp.take_along_axis(
            page_tbl, jnp.clip(tail // bpp, 0, page_tbl.shape[1] - 1)[:, None],
            axis=1)[:, 0] * bpp + tail % bpp
        rem = n - tail * blk
        qg = q.reshape(B, H, Hq // H, D)
        if use_kernel is None:
            use_kernel = kernel_ok(qg, k_pool, blk)
        if use_kernel:
            if interpret is None:
                interpret = _interpret_default()
            out = _sparse_pallas(qg, k_pool, v_pool, phys.astype(jnp.int32),
                                 cnt.astype(jnp.int32), last.astype(jnp.int32),
                                 rem.astype(jnp.int32), blk, interpret)
        else:
            out = _sparse_dense(qg, k_pool, v_pool, phys, cnt, last, rem, blk)
        return out.reshape(B, Hq, D)


# ---------------------------------------------------------- prefill chunk
def sparse_chunk_attention(q, k_pool, v_pool, ck_pool, off, page_row, spec):
    """A chunk of T queries of ONE sequence at positions off .. off + T - 1,
    after its K, V and compressed keys were written.  q [1, T, Hq, D], off
    int32 [1], page_row [1, M].  Every query selects for itself; the chunk
    then attends the sequence's gathered context under the selections' mask,
    `_Q_SUB` queries at a time (a masked pass: the chunk's operations are
    those of dense attention over the table's length).  Returns
    (out [1, T, Hq, D], picked [T]: the blocks each query's list holds)."""
    from .decode_attention import gather_pages

    _, T, Hq, D = q.shape
    H = k_pool.shape[1]
    rep, blk = Hq // H, spec.block_size
    ck = gather_compressed(ck_pool, page_row)                   # [1, H, I, D]
    n = off.reshape(1, 1) + jnp.arange(1, T + 1, dtype=jnp.int32)[None, :]
    qg = q.reshape(1, T, H, rep, D)
    idx, cnt, picked = select_blocks(qg, ck, n, spec)
    idx, cnt = idx[0], cnt[0]                                   # [T,H,K], [T]
    with jax.named_scope("sparse_chunk_attention"):
        k = gather_pages(k_pool, page_row)[0]                   # [H, L, D]
        v = gather_pages(v_pool, page_row)[0]
        L = k.shape[1]
        nb = L // blk
        live = jnp.arange(idx.shape[-1])[None, None, :] < cnt[:, None, None]
        sel = jnp.zeros((T, H, nb + 1), bool).at[
            jnp.arange(T)[:, None, None], jnp.arange(H)[None, :, None],
            jnp.where(live, idx, nb)].set(True)[..., :nb]       # [T, H, nb]
        sub = max(d for d in range(1, min(_Q_SUB, T) + 1) if T % d == 0)

        def part(args):
            qs, ss, ns = args          # [sub,H,rep,D], [sub,H,nb], [sub]
            s = _einsum_f32("thrd,hld->thrl", qs, k) / D ** 0.5
            ok = jnp.repeat(ss, blk, axis=-1) \
                & (jnp.arange(L)[None, None, :] < ns[:, None, None])
            ok = ok[:, :, None, :]
            s = jnp.where(ok, s, NEG_INF)
            p = jnp.where(ok, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
            p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
            return _einsum_f32("thrl,hld->thrd", p.astype(v.dtype), v).astype(q.dtype)

        out = jax.lax.map(part, (qg[0].reshape(T // sub, sub, H, rep, D),
                                 sel.reshape(T // sub, sub, H, nb),
                                 n[0].reshape(T // sub, sub)))
        return out.reshape(1, T, Hq, D), picked[0]
