"""Pallas TPU decode attention: one query a row against a static kv-cache,
and ragged query blocks against a PAGED one.

Reference gap: the snapshot has no decode-path attention at all (its
AnalysisPredictor era predates kv-cache serving); the XLA-composed decode
attention this replaces reads the head-minor [B, L, H, D] cache through
strided gathers and realizes well under half of the chip's streaming
bandwidth.  These kernels own the decode hot loops instead.

`decode_attention` (kernel `decode_attention`, the compiled generate()
loop of models/generation.py):

- the static cache is HEAD-MAJOR [B, H, L, D]: each (batch, head) grid point
  streams its keys/values as one contiguous [L, D] block (minor dims satisfy
  the (8, 128) Mosaic tile) — no relayout between HBM and the VPU;
- online softmax over key blocks (the flash recipe at query-length 1);
- optional int8 cache: the kernel dequantizes INSIDE VMEM against
  per-(head, token) scales, so the int8 cache HALVES the HBM bytes decode
  actually streams — on XLA the dequantized bf16 buffer materializes to HBM
  and int8 was a capacity-only lever (models/kv_cache.py history);
- GQA folds into the BlockSpec index map (query head h reads kv head
  h // rep) — kv blocks are fetched once per query head with no repeated
  materialization;
- the valid-length mask rides a scalar-prefetch argument, replacing the
  [1, 1, S, L] additive-mask tensor the composed path rebuilt every step.

`paged_decode_attention` (kernel `paged_attention`, every attention call of
LLMEngine's decode, prefill-chunk and verify programs): a page pool
[P, Hkv, page_size, D] read through per-slot page tables.  The grid is over
slots; a slot walks its OWN pages once — a loop whose trip count comes from
the slot's length, not from the table's width — in groups of whole pages
fetched by explicit double-buffered copies, each group scored at once; a
masked slot walks nothing.  See the block above `gather_pages`.

Forward-only by design: decode runs under no_grad.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import metrics as _obs
from ._prng import interpret_default as _interpret_default

NEG_INF = -1e30

#: Dispatch decisions are made at TRACE time (the kernel/fallback choice is
#: shape-static), so the counter ticks once per attention call site per
#: compiled program — a fallback regression shows up as `path="paged_dense"`
#: increments on /metrics the moment the offending program compiles, not as
#: a silent latency cliff.  reason ∈ {tile_aligned, query_blocks, off_tile,
#: grid_too_large, forced}.  models/llama.py counts its Q/K/V projection
#: path here too: path ∈ {qkv_one_product, qkv_three_products}.
_M_ATTN_DISPATCH = _obs.counter(
    "llm_attn_kernel_total",
    "Attention dispatch decisions at trace time: which path (Pallas kernel "
    "vs dense fallback) served an attention call site and why",
    labelnames=("path", "reason"))

#: Test hook: "dense" forces every dispatcher onto the fallback path (used
#: by the kernel-vs-fallback engine parity suite and bench.py's ragged
#: round to A/B the SAME shapes through both paths).  None = normal
#: shape-based dispatch.
_FORCE_PATH = None


def _note(path, reason):
    _M_ATTN_DISPATCH.labels(path=path, reason=reason).inc()


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, kw_ref, vw_ref, *,
                   bk, L, G, rep, scale, quant, ks_ref=None, vs_ref=None):
    """One (batch, kv-head-group) grid point: G*rep query heads against their
    G kv heads' [L, D] caches.  Grouping amortizes the per-grid-point DMA +
    dispatch overhead ~G*x vs the old per-(batch, head) grid (measured 0.165
    -> ~0.04 ms/layer/step at B8 H16 L1152).  int8 caches dequantize ONCE
    into VMEM scratch before the block loop — the in-loop cast was VPU-bound
    and serialized against the dots (isolated: 300 -> 142 us)."""
    H = G * rep
    valid = len_ref[pl.program_id(0)]
    nkb = L // bk
    D = q_ref.shape[-1]
    Hp = q_ref.shape[-2]  # H padded to the 8-sublane tile

    if quant:
        kw_ref[...] = k_ref[0].astype(jnp.bfloat16)
        vw_ref[...] = v_ref[0].astype(jnp.bfloat16)
        kb, vb = kw_ref, vw_ref
    else:
        kb, vb = k_ref, v_ref

    def body(kj, carry):
        m, l, acc = carry  # [H, 1], [H, 1], [H, D] f32
        rows_s = []
        for g in range(G):
            if quant:
                kg = kb[g, pl.ds(kj * bk, bk), :]
            else:
                kg = kb[0, g, pl.ds(kj * bk, bk), :]
            for r in range(rep):
                h = g * rep + r
                qh = q_ref[0, 0, h:h + 1, :]  # [1, D]
                s = jax.lax.dot_general(qh, kg, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                rows_s.append(s)
        s = jnp.concatenate(rows_s, axis=0) * scale  # [H, bk]
        if quant:
            rows = bk // 128
            ks = ks_ref[0, :, pl.ds(kj * rows, rows), :].reshape(G, bk)
            s = s * jnp.repeat(ks, rep, axis=0) if rep > 1 else s * ks
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # [H, bk] f32
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            vs = vs_ref[0, :, pl.ds(kj * rows, rows), :].reshape(G, bk)
            p = p * jnp.repeat(vs, rep, axis=0) if rep > 1 else p * vs
        pb = p.astype(jnp.bfloat16 if quant else vb.dtype)
        outs = []
        for g in range(G):
            if quant:
                vg = vb[g, pl.ds(kj * bk, bk), :]
            else:
                vg = vb[0, g, pl.ds(kj * bk, bk), :]
            outs.append(jax.lax.dot_general(
                pb[g * rep:(g + 1) * rep], vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        pv = jnp.concatenate(outs, axis=0)  # [H, D]
        acc = acc * corr + pv
        return m_new, l, acc

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, acc0))
    out = (acc / l).astype(o_ref.dtype)
    if Hp != H:
        out = jnp.concatenate(
            [out, jnp.zeros((Hp - H, D), o_ref.dtype)], axis=0)
    o_ref[0, 0] = out


def _pick_group(Hkv, L, D, quant):
    """kv heads per grid point: largest divisor of Hkv whose blocks (plus the
    dequant scratch for int8) stay within ~6 MB of VMEM."""
    per_head = L * D * (1 if quant else 2) * 2          # k + v blocks
    scratch = L * D * 2 * 2 if quant else 0             # bf16 dequant scratch
    for g in (16, 8, 4, 2, 1):
        if Hkv % g == 0 and g * (per_head + scratch) <= 6 * 1024 * 1024:
            return g
    return 1


def _decode_pallas(q, k, v, offset, k_scale, v_scale, scale, bk, interpret):
    B, S, H, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    rep = H // Hkv
    quant = k_scale is not None
    valid = jnp.broadcast_to(
        jnp.asarray(offset, jnp.int32) + S, (B,)).astype(jnp.int32)
    # head-major query so every block's trailing dims are tile-clean
    q = jnp.transpose(q, (0, 2, 1, 3))  # [B, H, 1, D]
    G = _pick_group(Hkv, L, D, quant)
    ng = Hkv // G
    Hg = G * rep  # query heads per grid point
    Hp = max(Hg, 8)  # sublane-tile floor for the per-group q/out blocks
    qg = q.reshape(B, ng, Hg, D)
    if Hp != Hg:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Hp - Hg), (0, 0)))

    # index maps receive the prefetched scalar ref as a trailing argument
    in_specs = [
        pl.BlockSpec((1, 1, Hp, D), lambda b, j, _len: (b, j, 0, 0)),
        pl.BlockSpec((1, G, L, D), lambda b, j, _len: (b, j, 0, 0)),
        pl.BlockSpec((1, G, L, D), lambda b, j, _len: (b, j, 0, 0)),
    ]
    args = [qg, k, v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, G, L // 128, 128), lambda b, j, _len: (b, j, 0, 0)),
            pl.BlockSpec((1, G, L // 128, 128), lambda b, j, _len: (b, j, 0, 0)),
        ]
        args += [k_scale.reshape(B, Hkv, L // 128, 128),
                 v_scale.reshape(B, Hkv, L // 128, 128)]

    def kernel(len_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, kw_ref, vw_ref = rest
        else:
            (o_ref,) = rest[:1]
            ks_ref = vs_ref = kw_ref = vw_ref = None
        return _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, kw_ref,
                              vw_ref, bk=bk, L=L, G=G, rep=rep, scale=scale,
                              quant=quant, ks_ref=ks_ref, vs_ref=vs_ref)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, ng),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, Hp, D), lambda b, j, _len: (b, j, 0, 0)),
            scratch_shapes=([pltpu.VMEM((G, L, D), jnp.bfloat16)] * 2
                            if quant else []),
        ),
        out_shape=jax.ShapeDtypeStruct((B, ng, Hp, D), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="decode_attention",
    )(valid, *args)
    out = out[:, :, :Hg, :].reshape(B, H, 1, D)
    return out.transpose(0, 2, 1, 3)  # [B, 1, H, D]


def _decode_dense(q, k, v, offset, k_scale, v_scale, scale):
    """XLA fallback (CPU tests, S > 1, odd shapes): same math, dense."""
    B, S, H, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    rep = H // Hkv
    if k_scale is not None:
        k = k.astype(q.dtype) * k_scale.astype(q.dtype)[..., None]
        v = v.astype(q.dtype) * v_scale.astype(q.dtype)[..., None]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bshd,bhld->bhsl", q, k).astype(jnp.float32) * scale
    kpos = jnp.arange(L)[None, None, None, :]
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim >= 1:  # per-slot offsets [B]
        off = off[:, None, None, None]
    qpos = off + jnp.arange(S)[None, None, :, None]
    s = jnp.where(kpos <= qpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhsl,bhld->bshd", p, v)


def decode_attention(q, k, v, offset, k_scale=None, v_scale=None, scale=None,
                     block_k=None, interpret=None):
    """Attention of q [B, S, H, D] against a head-major static cache
    k/v [B, Hkv, L, D] whose first `offset + s` positions are valid for
    query position s.  int8 caches pass per-(head, token) scales [B, Hkv, L].
    Returns [B, S, H, D] in q's dtype."""
    B, S, H, D = q.shape
    L = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    bk = block_k
    if bk is None:
        for cand in (512, 384, 256, 128):
            if L % cand == 0:
                bk = cand
                break
    shapes_ok = (S == 1 and D % 128 == 0 and bk is not None
                 and L % bk == 0 and H % k.shape[1] == 0
                 and (k_scale is None or L % 128 == 0))
    # Measured on v5e (same-session A/B, 12-layer 738M decode, P=1024):
    #   int8:  kernel 3.7 ms/tok vs dense-XLA 6.8 (the XLA path materializes
    #          the dequantized bf16 cache in HBM) -> kernel always.
    #   bf16:  kernel 3.5 vs dense 3.8 at B=8, but dense 6.8 vs kernel 9.6 at
    #          B=32 (the per-(b,h) DMA grid stops amortizing) -> kernel only
    #          while the grid stays small.
    use_kernel = shapes_ok and (k_scale is not None or B * H <= 192)
    if _FORCE_PATH == "dense":
        use_kernel = False
        reason = "forced"
    elif use_kernel:
        reason = "tile_aligned"
    elif not shapes_ok:
        reason = "multi_query" if S != 1 else "off_tile"
    else:
        reason = "grid_too_large"
    if use_kernel:
        _note("static_kernel", reason)
        return _decode_pallas(q, k, v, offset, k_scale, v_scale, scale, bk,
                              interpret)
    _note("static_dense", reason)
    return _decode_dense(q, k, v, offset, k_scale, v_scale, scale)


# ------------------------------------------------------------------- paged
#
# Ragged paged attention (the arxiv 2604.15464 design, adapted to this
# stack's head-major page layout): the kv cache is a global page pool
# [P, Hkv, page_size, D] plus per-slot page tables [B, max_pages] — capacity
# scales with ACTUAL sequence lengths, not max_seq_len.  ONE kernel serves
# every ragged query-block shape the serving engine produces: S=1 continuous
# -batching decode, prefill chunks of S=C tokens at arbitrary per-slot chunk
# offsets, and the S=K+1 speculative-verify ladder — the per-slot (offset,
# query-length) pair rides the scalar-prefetched `lengths` vector
# (lengths[b] = offset[b] + S) and drives a per-ROW causal mask inside the
# online-softmax loop: query s of slot b attends keys
# [0, lengths[b] - S + s].  The grid is over slots (and kv-head groups where
# a query block's state does not fit VMEM at every head); the pools stay in
# HBM, and a grid step walks ITS slot's pages once: a loop whose trip count
# is the slot's own ceil(lengths[b] / (pages a step * page_size)), each
# step fetching a group of whole pages through the scalar-prefetched table
# by explicit copies (the next group in flight while this one is scored).
# A table's width costs nothing: entries past the slot's last page are
# never read, fetched or visited.  A slot whose table OPENS on the trash
# page (kv_cache.TRASH_PAGE: the engine masks idle and mid-prefill slots
# so) walks nothing, whatever stale position it is handed, and gets zeros.

TRASH_PAGE = 0  # models.kv_cache.TRASH_PAGE: never allocated
#: what _pick_walk_paged's bounds add up to (4 MB of page buffers, 6 of query
#: state, the 8 MB score tile) and the compiler's own copies of that tile
#: (probabilities, their cast): 16.3 MB at the widest shape compiled
_PAGED_VMEM_LIMIT = 40 * 1024 * 1024


def gather_pages(pool, page_tbl):
    """[P, H, ps, D] pool + [B, M] table -> contiguous [B, H, M*ps, D]
    (scale pools [P, H, ps] -> [B, H, M*ps]).  The dense fallback's view of
    the paged cache; also the test oracle."""
    g = pool[page_tbl]  # [B, M, H, ps, ...]
    if g.ndim == 5:
        B, M, H, ps, D = g.shape
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(B, H, M * ps, D)
    B, M, H, ps = g.shape
    return jnp.transpose(g, (0, 2, 1, 3)).reshape(B, H, M * ps)


def _paged_kernel(len_ref, pt_ref, q_ref, k_hbm, v_hbm, *refs, ps, M, S, G,
                  W, rep, scale, quant):
    """One (slot, kv-head-group) grid step: walk the slot's pages in groups
    of W, folding each group's W*ps keys and values into the online-softmax
    state (m/l/acc VMEM scratch).  The query block is RAGGED: its rows are
    laid out [G kv heads, S query positions, rep query heads] (row
    g*S*rep + s*rep + r is query position s of query head g*rep + r), so
    one [S*rep, D] x [W*ps, D]^T dot per kv head scores every query row of
    that head against the whole group, and a per-row causal threshold
    lengths[b] - S + s + 1 masks each row to its own prefix — S=1 decode,
    prefill chunks, and the K+1 verify ladder are the SAME kernel at
    different static S.  int8 pages dequantize in VMEM: payload cast once
    per group, per-(head, token) scales applied to the score/probability
    rows outside the dots (the static kernel's recipe).

    Two buffers of W pages a pool: while a group is scored the next is in
    flight, and under a step's LAST group that is the first group of the
    grid step after it (the grid runs in order; `at_ref` hands on which
    buffer it went to), so only the call's very first fetch is waited for
    with nothing to do."""
    # inputs continue with the scale pools, THEN output + scratch
    pools = (k_hbm, v_hbm) + (tuple(refs[:2]) if quant else ())
    o_ref, m_ref, l_ref, acc_ref, sem, at_ref, kbuf, vbuf, *sbufs = \
        refs[len(pools) - 2:]
    ksbuf, vsbuf = sbufs or (None, None)  # the scale pages' buffers (int8)
    j, b = pl.program_id(0), pl.program_id(1)
    ng, B = pl.num_programs(0), pl.num_programs(1)
    r = b % q_ref.shape[0]  # this slot's place in the resident q/out block
    KB = W * ps           # keys per loop step
    sg = S * rep          # query rows per kv head
    rows = G * sg         # query rows per grid step
    D = q_ref.shape[-1]
    Rp = q_ref.shape[-2]  # rows padded to the 8-sublane tile

    def walk(bb):
        """(keys, pages) slot bb's walk covers: none where its table opens
        on the trash page, no more pages than the table holds."""
        n = jnp.where(pt_ref[bb * M] == TRASH_PAGE, 0, len_ref[bb])
        return n, jnp.minimum((n + ps - 1) // ps, M)

    def copies(bb, jj, slot, e, i):
        """Entry i of slot bb's table, head group jj, every pool -> place e
        of buffer `slot`."""
        page, heads = pt_ref[bb * M + i], pl.ds(jj * G, G)
        keys = pl.ds(pl.multiple_of(e * ps, ps), ps)
        dst = [buf.at[slot, :, keys] for buf in (kbuf, vbuf)]
        dst += [buf.at[slot, e] for buf in sbufs]
        return [pltpu.make_async_copy(hbm.at[page, heads], d, sem.at[slot, e, n])
                for n, (hbm, d) in enumerate(zip(pools, dst))]

    def start(bb, jj, pages, slot, grp):
        """Group `grp` of a walk of `pages` pages -> buffer `slot`: the
        entries the walk has, none where it has none.  (Every condition
        here is a trip count: a `pl.when` an entry cost seconds to trace.)"""
        def one(e, _):
            for c in copies(bb, jj, slot, e, grp * W + e):
                c.start()
        jax.lax.fori_loop(0, jnp.clip(pages - grp * W, 0, W), one, None)

    valid, npages = walk(b)
    ngrp = (npages + W - 1) // W
    # the grid step after this one, whose first group this one fetches
    nb = jnp.where(b + 1 == B, 0, b + 1)
    nj = jnp.where(b + 1 == B, j + 1, j)
    npages_next = jnp.where(nj < ng, walk(nb)[1], 0)

    first = (b == 0) & (j == 0)  # nobody fetched this step's first group
    start(b, j, jnp.where(first, npages, 0), 0, 0)
    at = jnp.where(first, 0, at_ref[0])  # the buffer that group is in
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-row causal end: row g*sg + s*rep + r is query position s, and
    # query s of a slot whose lengths entry is `valid` = offset + S may
    # read keys [0, offset + s] — i.e. kpos < valid - S + s + 1.  Row 0
    # always has offset + 1 >= 1 valid keys, so group 0 (the only group
    # guaranteed to be walked) leaves no row's running max at NEG_INF.
    # Nothing past the pages walked is a key, whatever `valid` says.
    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    qend = jnp.minimum(valid - S + (ri // rep) % S + 1, npages * ps)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
    cast = (lambda x: x.astype(jnp.bfloat16)) if quant else (lambda x: x)

    def body(grp, _):
        slot = (at + grp) % 2

        # under this group the next is fetched: this walk's, or after
        # its last group the first of the grid step after this one
        last = grp + 1 == ngrp
        start(jnp.where(last, nb, b), jnp.where(last, nj, j),
              jnp.where(last, npages_next, npages), 1 - slot,
              jnp.where(last, 0, grp + 1))
        live = jnp.minimum(npages - grp * W, W)

        def wait(e, _):
            for c in copies(b, j, slot, e, 0):
                c.wait()

        # an entry past the slot's last page is not fetched: its keys are
        # masked, but a probability of 0 times whatever the buffer held
        # (NaN bits, at worst) is not 0, so its values are zeroed
        def zero(e, _):
            if quant:
                vsbuf[slot, e] = jnp.zeros(vsbuf.shape[2:], vsbuf.dtype)
            else:
                vbuf[slot, :, pl.ds(pl.multiple_of(e * ps, ps), ps)] = \
                    jnp.zeros((G, ps, D), vbuf.dtype)

        jax.lax.fori_loop(0, live, wait, None)
        jax.lax.fori_loop(live, W, zero, None)

        def scales(buf):  # [W, G, ps/128, 128] -> [rows, KB]
            sc = jnp.concatenate(
                [buf[slot, e].reshape(G, ps) for e in range(W)], axis=1)
            return jnp.repeat(sc, sg, axis=0) if sg > 1 else sc

        rows_s = [jax.lax.dot_general(
            q_ref[r, 0, g * sg:(g + 1) * sg, :], cast(kbuf[slot, g]),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for g in range(G)]
        s = (jnp.concatenate(rows_s, axis=0) if G > 1
             else rows_s[0]) * scale  # [rows, KB]
        if quant:
            s = s * scales(ksbuf)
        s = jnp.where(col < qend - grp * KB, s, NEG_INF)
        m_prev = m_ref[:rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)  # [rows, KB] f32
        corr = jnp.exp(m_prev - m_new)
        l_ref[:rows, :1] = l_ref[:rows, :1] * corr + jnp.sum(
            pexp, axis=1, keepdims=True)
        m_ref[:rows, :1] = m_new
        if quant:
            pexp = pexp * scales(vsbuf)
        pb = pexp.astype(jnp.bfloat16 if quant else vbuf.dtype)
        outs = [jax.lax.dot_general(
            pb[g * sg:(g + 1) * sg], cast(vbuf[slot, g]),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for g in range(G)]
        pv = jnp.concatenate(outs, axis=0) if G > 1 else outs[0]  # [rows, D]
        acc_ref[:rows, :] = acc_ref[:rows, :] * corr + pv

    jax.lax.fori_loop(0, ngrp, body, None)

    # a step that walked nothing still hands the next one its first group
    start(nb, nj, jnp.where(ngrp == 0, npages_next, 0), at, 0)
    at_ref[0] = (at + ngrp) % 2
    l = l_ref[:rows, :1]
    out = (acc_ref[:rows, :] / jnp.where(l <= 0.0, 1.0, l)).astype(o_ref.dtype)
    if Rp != rows:
        out = jnp.concatenate(
            [out, jnp.zeros((Rp - rows, D), o_ref.dtype)], axis=0)
    o_ref[r, 0] = out


def _paged_state_bytes(rows, D):
    """VMEM bytes of the per-grid-step ragged query state: the q block plus
    the f32 m/l/acc online-softmax scratch (shared bound between the walk
    picker and the dispatcher's S cap)."""
    return rows * (4 * D            # q block (f32 worst case)
                   + 2 * 4 * 128    # m + l scratch rows
                   + 4 * D)         # acc scratch


def _pick_walk_paged(Hkv, ps, D, quant, S, rep, M):
    """(kv heads a grid step, pages a loop step).  Heads first: a page's
    heads are one contiguous block, so a decode row's copies are whole
    pages; then the widest step of at most M pages: a wide step amortises
    the per-row work of the soft-max state (a chunk's 2,048 rows pay it a
    step, whatever the step holds), and what a row's last step holds past
    its last page costs no bytes.  The bounds are the two page buffers of
    every pool, the G*S*rep query rows of q/m/l/acc state, and the
    [rows, keys] float32 score tile."""
    mib = 1024 * 1024
    page = ps * D * (1 if quant else 2) * 2 * 2  # k + v, two buffers, a head

    def fits(g, w):
        rows = g * S * rep
        return (Hkv % g == 0 and w <= M and g * w * page <= 4 * mib
                and _paged_state_bytes(rows, D) <= 6 * mib
                and rows * w * ps * 4 <= 8 * mib)

    G = next((g for g in (16, 8, 4, 2, 1) if fits(g, 1)), 1)
    return G, next((w for w in (8, 4, 2) if fits(G, w)), 1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_pallas(q, k_pages, v_pages, lengths, page_tbl, k_scale, v_scale,
                  scale, interpret):
    """A jit of its own: a program with a call site a layer lowers the
    kernel once."""
    B, S, H, D = q.shape
    Hkv, ps = k_pages.shape[1], k_pages.shape[2]
    M = page_tbl.shape[1]
    rep = H // Hkv
    quant = k_scale is not None
    G, W = _pick_walk_paged(Hkv, ps, D, quant, S, rep, M)
    ng = Hkv // G
    rows = G * S * rep
    Rp = max(8, -(-rows // 8) * 8)  # 8-sublane tile floor for q/out blocks
    # ragged row layout [G, S, rep]: query head h = j*G*rep + g*rep + r of
    # position s lands at row g*S*rep + s*rep + r of group j — each kv
    # head's S*rep query rows are contiguous, so the kernel scores them
    # with ONE dot per kv head (at S=1 this is exactly the old [G, rep]
    # head order)
    qg = jnp.transpose(q, (0, 2, 1, 3))        # [B, H, S, D]
    qg = qg.reshape(B, ng, G, rep, S, D)
    qg = jnp.transpose(qg, (0, 1, 2, 4, 3, 5)).reshape(B, ng, rows, D)
    if Rp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Rp - rows), (0, 0)))
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    page_tbl = jnp.asarray(page_tbl, jnp.int32).reshape(-1)

    # the prefetched (lengths, flat page table) refs reach the index maps
    # last and the kernel first; the pools are handed over where they lie.
    # Slots are the grid's inner axis and RB of them share one q/out block:
    # the pipeline moves a block when its index changes, once in RB steps,
    # where a block a step costs every step a copy's latency
    RB = max(d for d in range(1, B + 1) if B % d == 0 and (
        d == 1 or d * Rp * D * 16 <= 4 * 1024 * 1024))
    qo_spec = pl.BlockSpec((RB, 1, Rp, D),
                           lambda g, b, _len, _pt: (b // RB, g, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    args = [qg, k_pages, v_pages]
    scratch = [pltpu.VMEM((Rp, 128), jnp.float32),
               pltpu.VMEM((Rp, 128), jnp.float32),
               pltpu.VMEM((Rp, D), jnp.float32),
               pltpu.SemaphoreType.DMA((2, W, 4 if quant else 2)),  # a pool
               pltpu.SMEM((1,), jnp.int32),
               pltpu.VMEM((2, G, W * ps, D), k_pages.dtype),
               pltpu.VMEM((2, G, W * ps, D), v_pages.dtype)]
    if quant:
        sb = ps // 128
        P = k_pages.shape[0]
        args += [k_scale.reshape(P, Hkv, sb, 128),
                 v_scale.reshape(P, Hkv, sb, 128)]
        scratch += [pltpu.VMEM((2, W, G, sb, 128), jnp.float32)] * 2

    kernel = functools.partial(_paged_kernel, ps=ps, M=M, S=S, G=G, W=W,
                               rep=rep, scale=scale, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ng, B),
            in_specs=[qo_spec] + [hbm] * (len(args) - 1),
            out_specs=qo_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, ng, Rp, D), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PAGED_VMEM_LIMIT),
        name="paged_attention",
    )(lengths, page_tbl, *args)
    out = out[:, :, :rows, :].reshape(B, ng, G, S, rep, D)
    out = jnp.transpose(out, (0, 1, 2, 4, 3, 5)).reshape(B, H, S, D)
    return out.transpose(0, 2, 1, 3)  # [B, S, H, D]


def _paged_dense(q, k_pages, v_pages, offset, page_tbl, k_scale, v_scale,
                 scale):
    """XLA fallback (CPU / odd page or head shapes): gather the slot's
    pages into a contiguous view, then the dense math.  The gather is
    CAPPED at the batch-max logical length when the offsets are concrete
    (page tables are padded to max_pages, but no slot can have valid keys
    past max(offset) + S): on a mixed-length batch in a long-max-len pool
    this trims the materialized view — and the O(S * M * ps) masked score
    matrix behind it — from every slot's FULL table to the pages anyone
    actually uses.  Traced offsets (shape-polymorphic callers) keep the
    full-table gather: the cap must be static to change the gather shape."""
    S, M, ps = q.shape[1], page_tbl.shape[1], k_pages.shape[2]
    if not isinstance(jnp.asarray(offset), jax.core.Tracer):
        used = min(M, -(-(int(np.max(np.asarray(offset))) + S) // ps))
        page_tbl = page_tbl[:, :max(used, 1)]
    k = gather_pages(k_pages, page_tbl)
    v = gather_pages(v_pages, page_tbl)
    if k_scale is not None:
        k = k.astype(q.dtype) * gather_pages(
            k_scale, page_tbl).astype(q.dtype)[..., None]
        v = v.astype(q.dtype) * gather_pages(
            v_scale, page_tbl).astype(q.dtype)[..., None]
        k_scale = v_scale = None
    return _decode_dense(q, k, v, offset, None, None, scale)


def pool_layout(kv_heads, head_dim, lanes=128):
    """(rows a token, row width) of a page pool for `kv_heads` heads of
    `head_dim`: heads narrower than a 128-lane row lie side by side on one,
    as many as fill it (two at head_dim 64), so the pool keeps the heads'
    own bytes and the kernel sees whole-lane rows; other heads a row each."""
    f = lanes // head_dim if head_dim < lanes and lanes % head_dim == 0 else 1
    if kv_heads % f:
        f = 1
    return kv_heads // f, head_dim * f


def _paged_packed(q, k_pages, v_pages, offset, page_tbl, k_scale, scale,
                  interpret):
    """Attention against a pool whose rows hold f = W / D heads side by side
    (`pool_layout`: kv head f*j + e in lanes [e*D, (e+1)*D) of row j).  Each
    query goes zero-padded into its kv head's lanes, so one W-wide product
    scores exactly q_h . k_h, and the paged path runs as for W-wide heads
    with f times as many queries a row; a query's output is its own lanes
    of the W-wide result (the others hold its row-mate head's values)."""
    B, S, H, D = q.shape
    rows, W = k_pages.shape[1], k_pages.shape[-1]
    f = W // D
    if W != f * D or k_scale is not None or H % (rows * f):
        raise ValueError(
            f"a pool of {W}-wide rows holds {D}-wide heads only {f} a row, "
            f"{H} query heads on {rows * f} kv heads and not int8 (a scale a "
            f"row would be two heads'); got q {q.shape}, pool {k_pages.shape}")
    lane = (np.arange(H) // (H // (rows * f))) % f  # a query head's place
    onehot = jnp.asarray(np.eye(f, dtype=np.float32)[lane], q.dtype)
    qp = (q[:, :, :, None, :] * onehot[:, :, None]).reshape(B, S, H, W)
    out = paged_decode_attention(qp, k_pages, v_pages, offset, page_tbl,
                                 scale=scale, interpret=interpret)
    return out.reshape(B, S, H, f, D)[:, :, np.arange(H), lane]


def paged_decode_attention(q, k_pages, v_pages, offset, page_tbl,
                           k_scale=None, v_scale=None, scale=None,
                           interpret=None):
    """Attention of q [B, S, H, D] against a PAGED cache: pool
    [P, Hkv, page_size, D] + page table [B, max_pages], with the first
    offset + s positions of each slot valid for query position s (offset a
    scalar or a per-slot [B] vector).  int8 pools pass per-(head, token)
    scale pools [P, Hkv, page_size].  Any S >= 1 rides the ONE ragged
    Pallas kernel on tile-aligned shapes — S=1 decode, prefill chunks,
    and the K+1 spec-verify ladder; the gathered dense path survives only
    for CPU-odd shapes (D/page off the 128 tile, mismatched head counts);
    a query block too large for VMEM goes through it as sub-blocks.  A
    pool whose rows are wider than D holds several heads a row
    (`pool_layout`, `_paged_packed`).  `scale` defaults to 1/sqrt(D).
    Returns [B, S, H, D] in q's dtype."""
    B, S, H, D = q.shape
    Hkv, ps = k_pages.shape[1], k_pages.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if k_pages.shape[-1] != D:
        return _paged_packed(q, k_pages, v_pages, offset, page_tbl, k_scale,
                             scale, interpret)
    if interpret is None:
        interpret = _interpret_default()
    lengths = jnp.broadcast_to(
        jnp.asarray(offset, jnp.int32), (B,)).astype(jnp.int32) + S
    # ps % 128 == 0 keeps every page block (and the reshaped scale pages)
    # on clean (sublane, 128-lane) tiles; anything else is fallback-only
    tile_ok = D % 128 == 0 and ps % 128 == 0 and H % Hkv == 0
    # the S*rep query rows of q/m/l/acc state must fit VMEM even at G=1.  A
    # block of many queries at a wide group (256 x 16 query heads a kv head)
    # overflows it and still rides the kernel, as sub-blocks of `sub`
    # queries: block [s0, s0 + sub) with lengths offset + s0 + sub IS a
    # ragged block of its own (the kernel's causal end is valid - S + s + 1).
    # One query's rows always fit, so a divisor is always found.
    sub = max(d for d in range(1, S + 1) if S % d == 0 and (
        d == 1 or _paged_state_bytes(d * (H // Hkv), D) <= 6 * 1024 * 1024))
    if _FORCE_PATH == "dense":
        reason, use_kernel = "forced", False
    elif not tile_ok:
        reason, use_kernel = "off_tile", False
    else:
        reason, use_kernel = ("tile_aligned" if sub == S else "query_blocks"), True
    if use_kernel:
        _note("paged_kernel", reason)
        if sub == S:
            return _paged_pallas(q, k_pages, v_pages, lengths, page_tbl,
                                 k_scale, v_scale, scale, interpret)
        return jnp.concatenate([
            _paged_pallas(q[:, s0:s0 + sub], k_pages, v_pages,
                          lengths - (S - s0 - sub), page_tbl,
                          k_scale, v_scale, scale, interpret)
            for s0 in range(0, S, sub)], axis=1)
    _note("paged_dense", reason)
    return _paged_dense(q, k_pages, v_pages, offset, page_tbl,
                        k_scale, v_scale, scale)
