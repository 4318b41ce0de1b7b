"""Routed experts of a mixture-of-experts layer: a grouped matmul over the
experts THIS device holds.

Every token is routed over all the model's experts; a device that holds the
experts [lo, hi) computes, for each (token, expert) pair whose expert it
holds,

    w * W2[e] relu(W1[e] u)^2                    (one up-projection), or
    w * W2[e] (silu(Wg[e] u) * W1[e] u)          (GATED: `w_gate` given)

and leaves out the pairs of absent experts (on another device they would be
that device's part).  No token is dropped: there is no capacity.  The pairs
are sorted by expert and laid out in row tiles of TILE rows that belong to
one expert each; the Pallas kernel ``moe_experts`` (``moe_glu_experts`` in
the gated form: one op, two names, so a trace tells the two apart) walks the
tiles, the tile -> expert map rides scalar prefetch, and an expert's two (or
three) matrices are fetched once for its run of tiles and not at all when
nobody chose it.  At a
decode tick's few rows an expert the kernel streams weights: its time is the
bytes of the experts touched.

``counts`` ([held experts + 1] int32) comes back for the engine's counters:
pairs by held expert over the rows marked real, and how many experts had any.

``route`` is the router both expert models call: sigmoid scores over all the
experts, the best `top_k` by score + correction bias, normalised, scaled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._prng import interpret_default as _interpret_default

#: VMEM for one expert's two matrices, double-buffered (2 x 2 x 10 MB at the
#: published 2688 x 1856; three of 3.1 MB in the gated form at 2048 x 768)
#: plus the row tiles
_VMEM_LIMIT = 56 * 1024 * 1024
HI = jax.lax.Precision.HIGHEST


def route(x, gate_weight, bias, top_k, norm_topk_prob, scaling):
    """x [T, h] -> (expert int32 [T, K], weight float32 [T, K]): scores
    sigmoid(W_r x) in float32 over ALL experts, the K best by score + bias
    (the bias chooses, it does not weigh), weights s / (sum s + 1e-20) x
    scaling."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), gate_weight.astype(jnp.float32),
            precision=HI))
        _, idx = jax.lax.top_k(s + bias, top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scaling


def _tile_rows(n_pairs):
    """Rows a tile: small at decode's handful a expert (the padding is
    written and read back), larger for a prefill chunk."""
    return 16 if n_pairs <= 512 else 64


def _kernel(te_ref, nt_ref, x_ref, w1_ref, w2_ref, o_ref):
    del te_ref

    @pl.when(pl.program_id(0) < nt_ref[0])
    def _():
        h = jax.lax.dot_general(x_ref[...], w1_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = jnp.square(jnp.maximum(h, 0.0)).astype(w2_ref.dtype)
        o_ref[...] = jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)


def _glu_kernel(te_ref, nt_ref, x_ref, wg_ref, w1_ref, w2_ref, o_ref):
    del te_ref

    @pl.when(pl.program_id(0) < nt_ref[0])
    def _():
        up = lambda w: jax.lax.dot_general(  # noqa: E731
            x_ref[...], w[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        h = (jax.nn.silu(up(wg_ref)) * up(w1_ref)).astype(w2_ref.dtype)
        o_ref[...] = jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)


def _grouped_pallas(xs, w1, w2, tile_expert, n_tiles, tm, interpret,
                    w_gate=None):
    """xs [tiles*tm, H] rows sorted into per-expert tiles -> float32
    [tiles*tm, H].  Tiles at and past `n_tiles` are not computed, and their
    block indices repeat the last real tile's, so nothing is fetched."""
    R, H = xs.shape
    F = w1.shape[1]
    last = lambda nt: jnp.maximum(nt[0] - 1, 0)  # noqa: E731
    row_map = lambda t, te, nt: (jnp.minimum(t, last(nt)), 0)  # noqa: E731
    expert = pl.BlockSpec((1, F, H), lambda t, te, nt: (te[t], 0, 0))
    mats = (w1, w2) if w_gate is None else (w_gate, w1, w2)
    return pl.pallas_call(
        _kernel if w_gate is None else _glu_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // tm,),
            in_specs=[pl.BlockSpec((tm, H), row_map)] + [expert] * len(mats),
            out_specs=pl.BlockSpec((tm, H), row_map),
        ),
        out_shape=jax.ShapeDtypeStruct((R, H), jnp.float32),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_experts" if w_gate is None else "moe_glu_experts",
    )(tile_expert, n_tiles, xs, *mats)


def _layout(local, n_held, tm):
    """Where each pair's row goes.  local [N] int32: the pair's expert among
    the held ones, or n_held for a pair nobody here serves.  Returns
    (dest [N] row of the tiled buffer, or `rows` for an unserved pair;
    tile_expert [tiles]; n_tiles [1])."""
    N = local.shape[0]
    tiles = -(-N // tm) + n_held  # every group wastes less than one tile
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[local].add(1)[:n_held]
    order = jnp.argsort(local, stable=True)
    start = jnp.cumsum(counts) - counts                   # first sorted pair
    group_tiles = -(-counts // tm)
    tile_end = jnp.cumsum(group_tiles)
    tile_start = tile_end - group_tiles
    sorted_local = local[order]
    held = sorted_local < n_held
    g = jnp.minimum(sorted_local, n_held - 1)
    rank = jnp.arange(N, dtype=jnp.int32) - start[g]
    dest_sorted = jnp.where(held, tile_start[g] * tm + rank, tiles * tm)
    dest = jnp.zeros((N,), jnp.int32).at[order].set(dest_sorted)
    n_tiles = tile_end[-1:]
    # tile t serves the first expert whose run of tiles ends past t; tiles
    # past the last real one repeat its expert, so no weights move for them
    t = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                    jnp.maximum(n_tiles[0] - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= t[:, None], axis=1), n_held - 1)
    return dest, tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32)


def _dense(x, w1, w2, local, weight, w_gate=None):
    """Fallback: every held expert over every token, masked.  For the CPU
    and for sizes the kernel's tiling does not fit."""
    n_held = w1.shape[0]
    f32 = jnp.float32
    cw = jnp.zeros((x.shape[0], n_held + 1), f32).at[
        jnp.arange(x.shape[0])[:, None], local].add(weight.astype(f32))[:, :n_held]
    # float32 operands: the CPU has no bf16 x bf16 -> f32 product
    h = jnp.einsum("th,efh->tef", x.astype(f32), w1.astype(f32))
    if w_gate is None:
        h = jnp.square(jnp.maximum(h, 0.0))
    else:
        h = jax.nn.silu(jnp.einsum("th,efh->tef", x.astype(f32),
                                   w_gate.astype(f32))) * h
    h = h.astype(w2.dtype).astype(f32)
    y = jnp.einsum("tef,efh->teh", h, w2.astype(f32))
    return jnp.einsum("teh,te->th", y, cw)


def kernel_ok(x, w1):
    """Tile alignment: the hidden size on whole lanes, the expert width on
    whole sublanes of the weights' dtype."""
    sub = 8 * 4 // jnp.dtype(w1.dtype).itemsize
    return x.shape[1] % 128 == 0 and w1.shape[1] % sub == 0


def moe_experts(x, w1, w2, expert, weight, lo, real=None, use_kernel=None,
                interpret=None, w_gate=None):
    """x [T, H]; w1 and w2 [held, F, H] (the up-projection as [out, in], the
    down-projection as [in, out]: H, a whole number of lanes, is minor in
    both): the experts [lo, lo + held) of the layer; expert int32 [T, K] and weight [T, K]: each
    token's choices among ALL experts and their normalised scores; real bool
    [T]: rows that are traffic (padding is computed but not counted);
    w_gate [held, F, H]: the gate of the gated form (silu(Wg u) * W1 u in
    place of relu(W1 u)^2), laid out as w1.
    Returns (float32 [T, H]: the held experts' part, counts [held + 1])."""
    T, K = expert.shape
    n_held = w1.shape[0]
    with jax.named_scope("moe_experts" if w_gate is None else "moe_glu_experts"):
        local = expert.astype(jnp.int32) - lo
        local = jnp.where((local >= 0) & (local < n_held), local, n_held)
        if use_kernel is None:
            use_kernel = kernel_ok(x, w1)
        if real is None:
            real = jnp.ones((T,), bool)
        counted = jnp.where(real[:, None], local, n_held).reshape(-1)
        counts = jnp.zeros((n_held + 1,), jnp.int32).at[counted].add(1)[:n_held]
        counts = jnp.concatenate([counts, jnp.sum(counts > 0, keepdims=True,
                                                  dtype=jnp.int32)])
        if not use_kernel:
            return _dense(x, w1, w2, local, weight, w_gate), counts
        if interpret is None:
            interpret = _interpret_default()
        tm = _tile_rows(T * K)
        dest, tile_expert, n_tiles = _layout(local.reshape(-1), n_held, tm)
        rows = tile_expert.shape[0] * tm
        # the token behind every row of the tiled buffer (T = a zero row)
        token = jnp.full((rows + 1,), T, jnp.int32).at[dest].set(
            jnp.repeat(jnp.arange(T, dtype=jnp.int32), K))[:rows]
        xs = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[token]
        ys = _grouped_pallas(xs, w1, w2, tile_expert, n_tiles, tm, interpret,
                             w_gate)
        ys = jnp.concatenate([ys, jnp.zeros((1, ys.shape[1]), ys.dtype)])
        picked = ys[dest].reshape(T, K, -1)   # unserved pairs read the zero row
        out = jnp.einsum("tkh,tk->th", picked, weight.astype(jnp.float32))
    return out, counts
