"""Pallas TPU flash attention (forward + backward).

Reference gap: the snapshot's only fused attention is a single-device CUDA kernel
(`/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu`, `fmha_ref.h`)
with no flash/online-softmax algorithm.  This is the TPU-native replacement: a
FlashAttention-2-style tiled kernel — online softmax over key blocks, O(S) memory,
logsumexp saved for a recompute-based backward — written against the MXU/VMEM model
(`/opt/skills/guides/pallas_guide.md`): [block_q, D] @ [D, block_k] contractions on
the MXU with f32 accumulators, K/V streamed block-by-block from VMEM.

Layout contract: paddle attention layout [B, S, H, D] at the API; kernels run on
[B*H, S, D].  Causal masking uses block-level early exit (upper-triangular key
blocks are never visited) plus an iota mask on the diagonal block.

On CPU (tests / debugging) the kernels run in Pallas interpret mode automatically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._prng import interpret_default as _interpret_default

NEG_INF = -1e30
# TPU vector lanes: the lse/dsum residuals are broadcast along a 128-lane minor dim
# so their block shapes satisfy the mosaic (8, 128) tiling rule (same trick as
# jax.experimental.pallas.ops.tpu.flash_attention MIN_BLOCK_SIZE).
LANES = 128


def _compiler_params(interpret):
    """All three kernels write disjoint output blocks along both grid axes."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq, bk, seq_q, seq_k):
    qi = pl.program_id(1)
    # keep matmul inputs in their storage dtype (bf16): the MXU contracts
    # bf16 x bf16 -> f32 at full rate; upcasting first forces f32 passes
    q = q_ref[0]  # [bq, D]
    nkb = pl.cdiv(seq_k, bk)
    # bottom-right alignment (matches the dense path): query i attends kpos <= i + off
    off = seq_k - seq_q
    if causal:
        # visit key blocks only up to (and including) this q block's diagonal
        nkb = jnp.minimum(nkb, ((qi + 1) * bq + off + bk - 1) // bk)

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kj * bk, bk), :]  # [bk, D]
        v = v_ref[0, pl.ds(kj * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos + off >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (bq, LANES))


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    grid = (BH, Sq // bq)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          seq_q=Sq, seq_k=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, LANES), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# -------------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
               scale, causal, bq, bk, seq_q, seq_k):
    # rest = (dlse_ref, dq_ref) for the lse-returning variant (ring combine
    # backprop), else (dq_ref,): the lse cotangent adds p * dlse to ds
    if len(rest) == 2:
        dlse_ref, dq_ref = rest
        dlse = dlse_ref[0][:, :1]
    else:
        (dq_ref,) = rest
        dlse = 0.0
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, :1]     # [bq, 1] (lanes-broadcast residual)
    dsum = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                   axis=-1, keepdims=True) - dlse
    nkb = pl.cdiv(seq_k, bk)
    off = seq_k - seq_q
    if causal:
        nkb = jnp.minimum(nkb, ((qi + 1) * bq + off + bk - 1) // bk)

    def body(kj, dq):
        k = k_ref[0, pl.ds(kj * bk, bk), :]
        v = v_ref[0, pl.ds(kj * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos + off >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk] f32
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - dsum)).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32) * scale

    dq = jax.lax.fori_loop(0, nkb, body,
                           jnp.zeros((bq, q.shape[-1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                scale, causal, bq, bk, seq_q, seq_k):
    if len(rest) == 3:
        dlse_ref, dk_ref, dv_ref = rest
    else:
        dlse_ref = None
        dk_ref, dv_ref = rest
    kj = pl.program_id(1)
    k = k_ref[0]   # [bk, D]
    v = v_ref[0]
    nqb = pl.cdiv(seq_q, bq)
    off = seq_k - seq_q
    start = jnp.maximum((kj * bk - off) // bq, 0) if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * bq, bq), :]
        do = do_ref[0, pl.ds(qi * bq, bq), :]
        o = o_ref[0, pl.ds(qi * bq, bq), :]
        lse = lse_ref[0, pl.ds(qi * bq, bq), :1]
        dsum = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                       axis=-1, keepdims=True)
        if dlse_ref is not None:
            dsum = dsum - dlse_ref[0, pl.ds(qi * bq, bq), :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos + off >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk] f32
        pc = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - dsum)).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32) * scale
        return dk, dv

    D = k.shape[-1]
    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, nqb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal, scale, bq, bk, interpret, dlse=None):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    lse_spec = pl.BlockSpec((1, bq, LANES), lambda bh, qi: (bh, qi, 0))
    lse_full = pl.BlockSpec((1, Sq, LANES), lambda bh, kj: (bh, 0, 0))
    dq_extra_in = [lse_spec] if dlse is not None else []
    dq_args = (q, k, v, o, do, lse) + ((dlse,) if dlse is not None else ())

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          seq_q=Sq, seq_k=Sk),
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            lse_spec,
        ] + dq_extra_in,
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
        name="flash_dq",
    )(*dq_args)

    dkv_extra_in = [lse_full] if dlse is not None else []
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          seq_q=Sq, seq_k=Sk),
        grid=(BH, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda bh, kj: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, kj: (bh, kj, 0)),
            pl.BlockSpec((1, Sq, D), lambda bh, kj: (bh, 0, 0)),
            pl.BlockSpec((1, Sq, D), lambda bh, kj: (bh, 0, 0)),
            lse_full,
        ] + dkv_extra_in,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, kj: (bh, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
        name="flash_dkv",
    )(*dq_args)
    return dq, dk, dv


# ---------------------------------------------------------------- public entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, causal, scale, bq, bk, interpret):
    o, _ = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret)
    return o


def _flash_bhsd_fwd(q, k, v, causal, scale, bq, bk, interpret):
    o, lse = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bhsd_bwd(causal, scale, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, causal, scale, bq, bk, interpret)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


# lse-returning entry for blockwise/ring combines: (o, lse) with a backward
# that honors the lse cotangent (d s_ij += p_ij * dlse_i, folded into dsum)
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd_lse(q, k, v, causal, scale, bq, bk, interpret):
    o, lse = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret)
    return o, lse[..., 0]


def _flash_bhsd_lse_fwd(q, k, v, causal, scale, bq, bk, interpret):
    o, lse = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret)
    return (o, lse[..., 0]), (q, k, v, o, lse)


def _flash_bhsd_lse_bwd(causal, scale, bq, bk, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse0 = cts
    dlse = jnp.broadcast_to(dlse0[..., None].astype(jnp.float32), lse.shape)
    return _flash_bwd(q, k, v, o, lse, do, causal, scale, bq, bk, interpret,
                      dlse=dlse)


_flash_bhsd_lse.defvjp(_flash_bhsd_lse_fwd, _flash_bhsd_lse_bwd)


def flash_attention_with_lse(q, k, v, causal=False, scale=None, block_q=None,
                             block_k=None, interpret=None):
    """Like flash_attention but also returns the per-query logsumexp
    [B, H, S] — the hook for blockwise combines (ring attention)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if interpret is None:
        interpret = _interpret_default()
    bq = min(block_q, Sq) if block_q else _auto_block(Sq)
    bk = min(block_k, Sk) if block_k else _auto_block(Sk)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    to_bhsd = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, x.shape[1], D)  # noqa: E731
    o, lse = _flash_bhsd_lse(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                             causal, float(scale), bq, bk, interpret)
    return (jnp.swapaxes(o.reshape(B, H, Sq, D), 1, 2),
            lse.reshape(B, H, Sq))


def supports_seq(seq):
    """Shapes the kernel handles without degenerate blocks (callers use this to
    gate flash vs dense SDPA)."""
    return seq % 128 == 0 or (seq <= 512 and seq % 8 == 0)


def _auto_block(seq):
    """Largest power-of-two block <= 512 dividing seq: big blocks amortize the
    per-grid-step overhead (measured on v5e: 512 beats 128 by ~25% at S=2048).
    Short sequences (<=512, 8-aligned) run as a single block; anything else is
    an error — tiny blocks would silently be 100x slower than dense SDPA."""
    for b in (512, 256, 128):
        if seq % b == 0:
            return b
    if seq <= 512 and seq % 8 == 0:
        return seq
    raise ValueError(
        f"flash_attention: sequence length {seq} is not divisible by a "
        f">=128 block (and too long for a single block) — pad the sequence "
        f"or use the dense SDPA path")


def flash_attention(q, k, v, causal=False, scale=None, block_q=None, block_k=None,
                    interpret=None):
    """q/k/v: [B, S, H, D] (paddle layout).  Returns [B, S, H, D].

    Requires S divisible by the block sizes and equal q/k head counts (the GQA
    repeat happens in the caller).  Differentiable via a recompute-based
    FlashAttention-2 backward.  Block sizes default to the largest power of two
    <= 512 dividing the sequence.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if causal and Sq > Sk:
        # queries 0..Sq-Sk-1 would attend zero keys (all-masked rows -> 0/0); the
        # dense path is the right tool for that degenerate shape
        raise ValueError(
            f"flash_attention(causal=True) requires Sq <= Sk, got Sq={Sq} Sk={Sk}; "
            "use the dense SDPA path")
    if interpret is None:
        interpret = _interpret_default()
    if block_q is None and block_k is None:
        from ..incubate import autotune as _autotune

        if _autotune.kernel_autotune_enabled():
            key = (Sq, Sk, D, bool(causal))
            cached = _autotune.flash_attention_block_cache.get(key)
            if cached is None and not isinstance(q, jax.core.Tracer):
                # first concrete call with this signature: measure candidates
                # (one-time compile cost per config, the phi autotune contract)
                sc = 1.0 / (D ** 0.5) if scale is None else float(scale)
                cached = _autotune.tune_flash_attention(
                    jnp.swapaxes(jnp.asarray(q), 1, 2).reshape(B * H, Sq, D),
                    jnp.swapaxes(jnp.asarray(k), 1, 2).reshape(B * H, Sk, D),
                    jnp.swapaxes(jnp.asarray(v), 1, 2).reshape(B * H, Sk, D),
                    causal, sc)
            if cached is not None:
                block_q, block_k = cached
    bq = min(block_q, Sq) if block_q else _auto_block(Sq)
    bk = min(block_k, Sk) if block_k else _auto_block(Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"seq lens ({Sq},{Sk}) must divide block sizes ({bq},{bk})")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    to_bhsd = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, x.shape[1], D)
    o = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                    causal, float(scale), bq, bk, interpret)
    return jnp.swapaxes(o.reshape(B, H, Sq, D), 1, 2)
