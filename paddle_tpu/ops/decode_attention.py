"""Pallas TPU single-query (decode) attention over a static kv-cache.

Reference gap: the snapshot has no decode-path attention at all (its
AnalysisPredictor era predates kv-cache serving); the XLA-composed decode
attention this replaces reads the head-minor [B, L, H, D] cache through
strided gathers and realizes well under half of the chip's streaming
bandwidth.  This kernel owns the decode hot loop instead:

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

Forward-only by design: decode runs under no_grad inside the compiled
generate() loop (models/generation.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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
#: grid_too_large, forced}.
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
# online-softmax page loop: query s of slot b attends keys
# [0, lengths[b] - S + s].  The kernel walks each slot's pages through the
# scalar-prefetched page table: the BlockSpec index map reads pt_ref[b, ·],
# so the pipeline DMAs exactly the pages the slot owns.  Slots shorter than
# max_pages point their unused table entries at the trash page
# (kv_cache.TRASH_PAGE) — the index map CLAMPS the walk to the slot's last
# valid page, so the ragged tail repeats a block index the pipeline has
# already fetched and the trash page is never DMA'd at all (trash-fetch
# elision; the tail compute is skipped by the valid-length gate).


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


def _paged_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, *refs, ps, S, G,
                  rep, scale, quant):
    """One (slot, kv-head-group, page) grid step: fold this page's keys and
    values into the slot's online-softmax state (m/l/acc VMEM scratch that
    persists across the sequential page axis).  The query block is RAGGED:
    its rows are laid out [G kv heads, S query positions, rep query heads]
    (row g*S*rep + s*rep + r is query position s of query head g*rep + r),
    so one [S*rep, D] x [ps, D]^T dot per kv head scores every query row of
    that head at once, and a per-row causal threshold
    lengths[b] - S + s + 1 masks each row to its own prefix — S=1 decode,
    prefill chunks, and the K+1 verify ladder are the SAME kernel at
    different static S.  int8 pages dequantize in VMEM: payload cast once
    per page, per-(head, token) scales applied to the score/probability
    rows outside the dots (the static kernel's recipe)."""
    if quant:  # inputs continue with the scale pages, THEN output + scratch
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    p = pl.program_id(2)
    M = pl.num_programs(2)
    valid = len_ref[b]
    sg = S * rep          # query rows per kv head
    rows = G * sg         # query rows per grid step
    D = q_ref.shape[-1]
    Rp = q_ref.shape[-2]  # rows padded to the 8-sublane tile

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p * ps < valid)
    def _page():
        if quant:
            kb = k_ref[0].astype(jnp.bfloat16)  # [G, ps, D]
            vb = v_ref[0].astype(jnp.bfloat16)
        else:
            kb, vb = k_ref[0], v_ref[0]
        rows_s = []
        for g in range(G):
            qg = q_ref[0, 0, g * sg:(g + 1) * sg, :]  # [S*rep, D]
            rows_s.append(jax.lax.dot_general(
                qg, kb[g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        s = (jnp.concatenate(rows_s, axis=0) if G > 1
             else rows_s[0]) * scale  # [rows, ps]
        if quant:
            ks = ks_ref[0].reshape(G, ps)
            s = s * (jnp.repeat(ks, sg, axis=0) if sg > 1 else ks)
        # per-row causal end: row g*sg + s*rep + r is query position s, and
        # query s of a slot whose lengths entry is `valid` = offset + S may
        # read keys [0, offset + s] — i.e. kpos < valid - S + s + 1.  Row 0
        # always has offset + 1 >= 1 valid keys, so page 0 (the only page
        # guaranteed to participate) leaves no row's running max at NEG_INF.
        ri = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        qend = valid - S + (ri // rep) % S + 1
        kpos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        s = jnp.where(kpos < qend, s, NEG_INF)
        m_prev = m_ref[:rows, :1]
        l_prev = l_ref[:rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)  # [rows, ps] f32
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pexp, axis=1, keepdims=True)
        if quant:
            vs = vs_ref[0].reshape(G, ps)
            pexp = pexp * (jnp.repeat(vs, sg, axis=0) if sg > 1 else vs)
        pb = pexp.astype(jnp.bfloat16 if quant else vb.dtype)
        outs = []
        for g in range(G):
            outs.append(jax.lax.dot_general(
                pb[g * sg:(g + 1) * sg], vb[g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        pv = jnp.concatenate(outs, axis=0) if G > 1 else outs[0]  # [rows, D]
        m_ref[:rows, :1] = m_new
        l_ref[:rows, :1] = l_new
        acc_ref[:rows, :] = acc_ref[:rows, :] * corr + pv

    @pl.when(p == M - 1)
    def _emit():
        l = l_ref[:rows, :1]
        out = (acc_ref[:rows, :]
               / jnp.where(l <= 0.0, 1.0, l)).astype(o_ref.dtype)
        if Rp != rows:
            out = jnp.concatenate(
                [out, jnp.zeros((Rp - rows, D), o_ref.dtype)], axis=0)
        o_ref[0, 0] = out


def _paged_state_bytes(rows, D):
    """VMEM bytes of the per-grid-step ragged query state: the q block plus
    the f32 m/l/acc online-softmax scratch (shared bound between the group
    picker and the dispatcher's S cap)."""
    return rows * (4 * D            # q block (f32 worst case)
                   + 2 * 4 * 128    # m + l scratch rows
                   + 4 * D)         # acc scratch


def _pick_group_paged(Hkv, ps, D, quant, S=1, rep=1):
    """kv heads per grid step: page blocks are small (one page, not the
    whole sequence), so the bounds are the double-buffered page pair
    staying comfortably inside VMEM plus — now that query blocks are
    ragged — the G*S*rep query rows of q/m/l/acc state."""
    per_head = ps * D * (1 if quant else 2) * 2  # k + v page blocks
    for g in (16, 8, 4, 2, 1):
        if (Hkv % g == 0 and g * per_head <= 2 * 1024 * 1024
                and _paged_state_bytes(g * S * rep, D) <= 6 * 1024 * 1024):
            return g
    return 1


def _paged_pallas(q, k_pages, v_pages, lengths, page_tbl, k_scale, v_scale,
                  scale, interpret):
    B, S, H, D = q.shape
    Hkv, ps = k_pages.shape[1], k_pages.shape[2]
    M = page_tbl.shape[1]
    rep = H // Hkv
    quant = k_scale is not None
    G = _pick_group_paged(Hkv, ps, D, quant, S, rep)
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
    page_tbl = jnp.asarray(page_tbl, jnp.int32)

    # Index maps receive the prefetched (lengths, page-table) refs last;
    # the page axis walks the slot's table — THE ragged gather.  Trash-fetch
    # elision: grid steps past the slot's last valid page CLAMP to that last
    # page, so the pipeline sees a repeated block index and skips the DMA
    # entirely (the valid-length gate already skips the compute) — the
    # ragged tail of a short slot in a long-max-len pool costs zero
    # bandwidth instead of one trash-page fetch per (slot, head-group).
    def _pidx(b, p, lens, pt):
        return pt[b, jnp.minimum(p, jnp.maximum(lens[b] - 1, 0) // ps)]

    in_specs = [
        pl.BlockSpec((1, 1, Rp, D), lambda b, g, p, _len, _pt: (b, g, 0, 0)),
        pl.BlockSpec((1, G, ps, D),
                     lambda b, g, p, lens, pt: (_pidx(b, p, lens, pt), g, 0, 0)),
        pl.BlockSpec((1, G, ps, D),
                     lambda b, g, p, lens, pt: (_pidx(b, p, lens, pt), g, 0, 0)),
    ]
    args = [qg, k_pages, v_pages]
    if quant:
        sb = ps // 128
        in_specs += [
            pl.BlockSpec((1, G, sb, 128),
                         lambda b, g, p, lens, pt: (_pidx(b, p, lens, pt), g, 0, 0)),
            pl.BlockSpec((1, G, sb, 128),
                         lambda b, g, p, lens, pt: (_pidx(b, p, lens, pt), g, 0, 0)),
        ]
        P = k_pages.shape[0]
        args += [k_scale.reshape(P, Hkv, sb, 128),
                 v_scale.reshape(P, Hkv, sb, 128)]

    kernel = functools.partial(_paged_kernel, ps=ps, S=S, G=G, rep=rep,
                               scale=scale, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, ng, M),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, Rp, D), lambda b, g, p, _len, _pt: (b, g, 0, 0)),
            scratch_shapes=[pltpu.VMEM((Rp, 128), jnp.float32),
                            pltpu.VMEM((Rp, 128), jnp.float32),
                            pltpu.VMEM((Rp, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, ng, Rp, D), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
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
        import numpy as np

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
    a query block too large for VMEM goes through it as sub-blocks.  Returns [B, S, H, D] in q's
    dtype."""
    B, S, H, D = q.shape
    Hkv, ps = k_pages.shape[1], k_pages.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
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
