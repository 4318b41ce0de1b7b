"""Pallas decode-attention kernel vs the dense oracle (interpret mode on CPU).

The kernel owns the generate() hot loop (ops/decode_attention.py): single
query against a head-major static cache, online softmax over key blocks,
valid-length masking via scalar prefetch, optional in-VMEM int8 dequant,
GQA through the BlockSpec index map."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.decode_attention import (
    _decode_dense, _decode_pallas, _paged_dense, _paged_pallas,
    _pick_walk_paged, decode_attention, gather_pages, paged_decode_attention)
from paddle_tpu.models.kv_cache import _quantize_kv

pytestmark = [pytest.mark.quick]


def _mk(B=2, H=8, Hkv=8, L=256, D=128, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(dtype) * 0.3)
    k = jnp.asarray(rng.randn(B, Hkv, L, D).astype(dtype) * 0.3)
    v = jnp.asarray(rng.randn(B, Hkv, L, D).astype(dtype) * 0.3)
    return q, k, v


def test_kernel_matches_dense():
    q, k, v = _mk()
    offset = 100
    got = _decode_pallas(q, k, v, offset, None, None, scale=1 / 128 ** 0.5,
                         bk=128, interpret=True)
    want = _decode_dense(q, k, v, offset, None, None, scale=1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_masks_by_valid_length():
    q, k, v = _mk()
    # poison the invalid tail: it must not leak into the output
    k = k.at[:, :, 120:, :].set(1e4)
    v = v.at[:, :, 120:, :].set(1e4)
    got = decode_attention(q, k, v, offset=119, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e2


def test_kernel_gqa_head_mapping():
    q, k, v = _mk(H=8, Hkv=2)
    got = _decode_pallas(q, k, v, 200, None, None, scale=0.1, bk=128,
                         interpret=True)
    want = _decode_dense(q, k, v, 200, None, None, scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_int8_dequant_in_kernel():
    q, k, v = _mk()
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    got = _decode_pallas(q, kq, vq, 180, ks, vs, scale=1 / 128 ** 0.5,
                         bk=128, interpret=True)
    # oracle: dense attention on the DEQUANTIZED cache
    kd = kq.astype(q.dtype) * ks[..., None]
    vd = vq.astype(q.dtype) * vs[..., None]
    want = _decode_dense(q, kd, vd, 180, None, None, scale=1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_dispatcher_falls_back_for_multi_query():
    q, k, v = _mk()
    q2 = jnp.concatenate([q, q], axis=1)  # S=2 -> dense path
    out = decode_attention(q2, k, v, offset=10, interpret=True)
    assert out.shape == (2, 2, 8, 128)
    # rows see strictly growing prefixes: position 1 attends one more key
    o0 = decode_attention(q, k, v, offset=10, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :1]), np.asarray(o0),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- ragged paged attention


def _mk_paged(B=3, H=8, Hkv=4, D=128, ps=128, M=4, seed=0,
              lens=(37, 300, 511), poison_trash=True):
    """Page pool + shuffled per-slot page tables with ragged lengths;
    unused table entries point at the (poisoned) trash page."""
    rng = np.random.RandomState(seed)
    P = 1 + B * M
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32) * 0.3)
    kp = jnp.asarray(rng.randn(P, Hkv, ps, D).astype(np.float32) * 0.3)
    vp = jnp.asarray(rng.randn(P, Hkv, ps, D).astype(np.float32) * 0.3)
    free = list(range(1, P))
    rng.shuffle(free)
    pt = np.zeros((B, M), np.int32)
    for b in range(B):
        for j in range(-(-(int(lens[b]) + 1) // ps)):
            pt[b, j] = free.pop()
    if poison_trash:  # a leak from the trash page would blow the output up
        kp = kp.at[0].set(1e4)
        vp = vp.at[0].set(1e4)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lens, jnp.int32)


def test_paged_kernel_matches_dense_gather_ragged():
    q, kp, vp, pt, lens = _mk_paged()
    got = _paged_pallas(q, kp, vp, lens + 1, pt, None, None,
                        scale=1 / 128 ** 0.5, interpret=True)
    want = _paged_dense(q, kp, vp, lens, pt, None, None, 1 / 128 ** 0.5)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_gqa_head_mapping():
    q, kp, vp, pt, lens = _mk_paged(H=8, Hkv=2, lens=(129, 64, 400))
    got = _paged_pallas(q, kp, vp, lens + 1, pt, None, None, scale=0.1,
                        interpret=True)
    want = _paged_dense(q, kp, vp, lens, pt, None, None, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_int8_dequant_in_kernel():
    q, kp, vp, pt, lens = _mk_paged(poison_trash=False)
    kq, ks = _quantize_kv(kp)
    vq, vs = _quantize_kv(vp)
    got = _paged_pallas(q, kq, vq, lens + 1, pt, ks, vs,
                        scale=1 / 128 ** 0.5, interpret=True)
    want = _paged_dense(q, kq, vq, lens, pt, ks, vs, 1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=4e-4, atol=4e-4)


def test_paged_matches_contiguous_static():
    """The paged path is numerically the static head-major path behind a
    page indirection: gather the pages and run the static dense oracle."""
    q, kp, vp, pt, lens = _mk_paged(poison_trash=False)
    got = paged_decode_attention(q, kp, vp, lens, pt, interpret=True)
    k = gather_pages(kp, pt)
    v = gather_pages(vp, pt)
    want = _decode_dense(q, k, v, lens, None, None, 1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_dispatcher_fallbacks():
    # S = 2 (chunked prefill) now rides the RAGGED kernel: strictly
    # growing per-row prefixes, row 0 equal to the S=1 call
    q, kp, vp, pt, lens = _mk_paged()
    q2 = jnp.concatenate([q, q], axis=1)
    out = paged_decode_attention(q2, kp, vp, lens, pt, interpret=True)
    assert out.shape == (3, 2, 8, 128)
    o0 = paged_decode_attention(q, kp, vp, lens, pt, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :1]), np.asarray(o0),
                               rtol=2e-5, atol=2e-5)
    # page size off the 128 tile -> dense path (still correct)
    q3, kp3, vp3, pt3, lens3 = _mk_paged(D=128, ps=32, M=8,
                                         lens=(5, 100, 200))
    got = paged_decode_attention(q3, kp3, vp3, lens3, pt3, interpret=True)
    want = _paged_dense(q3, kp3, vp3, lens3, pt3, None, None, 1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------- ragged S >= 1 query blocks
#
# ONE kernel serves S=1 decode, prefill chunks at arbitrary offsets, and
# the K+1 spec-verify ladder: per-slot lengths (= offset + S) prefetched
# into the kernel drive a per-ROW causal mask.  Every test pits the
# interpret-mode kernel against the gathered dense fallback on the SAME
# poisoned-trash page pool.


def _mk_ragged_q(B, S, H, D=128, seed=3):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)


@pytest.mark.parametrize("offs", [(123, 253, 380),   # straddle page edges
                                  (0, 127, 256)])    # incl. offset 0 / edge
def test_paged_kernel_ragged_verify_ladder(offs):
    """S = K+1 verify shape with per-slot offsets (the spec-decode tick)."""
    S = 5
    q, kp, vp, pt, lens = _mk_paged(lens=tuple(o + S for o in offs))
    qs = _mk_ragged_q(3, S, 8)
    off = jnp.asarray(offs, jnp.int32)
    got = _paged_pallas(qs, kp, vp, off + S, pt, None, None,
                        scale=1 / 128 ** 0.5, interpret=True)
    want = _paged_dense(qs, kp, vp, off, pt, None, None, 1 / 128 ** 0.5)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_ragged_chunk_gqa():
    """A full prefill chunk (S = 128) at a mid-page chunk offset, GQA
    rep = 4 — the chunked-prefill shape."""
    S, off = 128, 200
    q, kp, vp, pt, lens = _mk_paged(Hkv=2, lens=(off + S,) * 3)
    qs = _mk_ragged_q(3, S, 8, seed=5)
    got = _paged_pallas(qs, kp, vp, jnp.full((3,), off + S, jnp.int32), pt,
                        None, None, scale=0.1, interpret=True)
    want = _paged_dense(qs, kp, vp, off, pt, None, None, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,off,reason", [(1, 300, "tile_aligned"),
                                          (256, 200, "query_blocks")])
def test_paged_kernel_wide_group_two_kv_heads(S, off, reason):
    """32 query heads on 2 K/V heads (16 : 1): a decode row, and a prefill
    chunk of 256 queries whose 4096 rows a K/V head ride the kernel as two
    sub-blocks of 128."""
    from paddle_tpu.observability import REGISTRY

    fam = REGISTRY.get("llm_attn_kernel_total")
    count = lambda: {l: c.value for l, c in fam.series()}.get(  # noqa: E731
        ("paged_kernel", reason), 0.0)
    q, kp, vp, pt, lens = _mk_paged(H=32, Hkv=2, lens=(off + S,) * 3)
    qs = _mk_ragged_q(3, S, 32, seed=11)
    before = count()
    got = paged_decode_attention(qs, kp, vp, off, pt, interpret=True)
    assert count() == before + 1
    want = _paged_dense(qs, kp, vp, off, pt, None, None, 1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_ragged_int8():
    """int8 dequant-in-VMEM with a ragged S=3 block and per-slot offsets."""
    S = 3
    q, kp, vp, pt, lens = _mk_paged(lens=(60 + S, 250 + S, 500 + S),
                                    poison_trash=False)
    kq, ks = _quantize_kv(kp)
    vq, vs = _quantize_kv(vp)
    qs = _mk_ragged_q(3, S, 8, seed=6)
    off = jnp.asarray((60, 250, 500), jnp.int32)
    got = _paged_pallas(qs, kq, vq, off + S, pt, ks, vs,
                        scale=1 / 128 ** 0.5, interpret=True)
    want = _paged_dense(qs, kq, vq, off, pt, ks, vs, 1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=4e-4, atol=4e-4)


def test_paged_ragged_rows_match_single_query_calls():
    """Cross-check without the dense oracle: row s of a ragged S-block
    equals an S=1 call at offset + s (the ladder IS S stacked decodes)."""
    S = 4
    q, kp, vp, pt, lens = _mk_paged(lens=(37 + S, 300 + S, 507 + S))
    qs = _mk_ragged_q(3, S, 8, seed=7)
    off = jnp.asarray((37, 300, 507), jnp.int32)
    got = _paged_pallas(qs, kp, vp, off + S, pt, None, None,
                        scale=1 / 128 ** 0.5, interpret=True)
    for s in range(S):
        solo = _paged_pallas(qs[:, s:s + 1], kp, vp, off + s + 1, pt,
                             None, None, scale=1 / 128 ** 0.5,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(got[:, s:s + 1]),
                                   np.asarray(solo), rtol=2e-5, atol=2e-5)


# ------------------------------- the walk: a row's own pages, in groups
#
# The kernel's grid has no page axis: a row walks ceil(keys / group) groups
# of `_pick_walk_paged(...)[1]` pages, fetched by explicit copies, and the
# last group of a grid step starts the first of the next.  These cases sit
# where that can go wrong: tables far wider than contexts, lengths at a
# group's edge, rows that walk nothing beside rows that do.


def _group_keys(Hkv, S, rep, M, quant=False):
    """Keys a loop step of the kernel holds at the test's shapes."""
    return _pick_walk_paged(Hkv, 128, 128, quant, S, rep, M)[1] * 128


def _walk_pair(q, kp, vp, off, pt, S=1, ks=None, vs=None, scale=1 / 128 ** 0.5):
    off = jnp.asarray(off, jnp.int32)
    got = _paged_pallas(q, kp, vp, off + S, pt, ks, vs, scale=scale,
                        interpret=True)
    want = _paged_dense(q, kp, vp, off, pt, ks, vs, scale)
    assert np.isfinite(np.asarray(got)).all()
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("lens", [(5, 100, 130),     # 1, 1 and 2 pages of 32
                                  (0, 127, 255)])    # one key; page edges
def test_paged_walk_table_far_wider_than_the_context(lens):
    q, kp, vp, pt, lens = _mk_paged(M=32, lens=lens)
    got, want = _walk_pair(q, kp, vp, lens, pt)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_paged_walk_lengths_around_a_group_boundary(delta):
    """Contexts of one and of two groups, a key short, exact, a key over:
    the trip count and the last group's mask both turn here."""
    M = 32
    KB = _group_keys(4, 1, 2, M)
    assert 2 * KB + 1 < M * 128
    q, kp, vp, pt, lens = _mk_paged(
        M=M, lens=(KB + delta - 1, 2 * KB + delta - 1, 37))
    got, want = _walk_pair(q, kp, vp, lens, pt)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("idle", [(1,), (0,), (2,), (0, 1), (0, 1, 2)])
def test_paged_walk_skips_rows_whose_table_opens_on_trash(idle):
    """A masked slot (idle, mid-prefill) keeps whatever position its last
    request left: it walks nothing, gets zeros, and the rows around it
    (whose first group the step before them fetches) read what they read
    without it."""
    q, kp, vp, pt, lens = _mk_paged(M=32, lens=(300, 3000, 511))
    live = [b for b in range(3) if b not in idle]
    alone, want = _walk_pair(q, kp, vp, lens, pt)
    got, _ = _walk_pair(q, kp, vp, lens, pt.at[np.asarray(idle)].set(0))
    assert (got[list(idle)] == 0).all()
    np.testing.assert_array_equal(got[live], alone[live])
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


def test_paged_walk_int8_across_a_group_boundary():
    """int8 pages and their scale pages (a fourth and fifth copy a page),
    a ragged S=3 block whose rows end either side of a group's edge."""
    S, M = 3, 16
    KB = _group_keys(4, S, 2, M, quant=True)
    offs = (KB - 2, KB + 40, 2 * KB - S)
    q, kp, vp, pt, lens = _mk_paged(M=M, lens=tuple(o + S - 1 for o in offs),
                                    poison_trash=False)
    kq, ks = _quantize_kv(kp)
    vq, vs = _quantize_kv(vp)
    got, want = _walk_pair(_mk_ragged_q(3, S, 8, seed=12), kq, vq, offs, pt,
                           S=S, ks=ks, vs=vs)
    np.testing.assert_allclose(got, want, rtol=4e-4, atol=4e-4)


def test_paged_walk_chunk_at_an_offset_inside_a_group():
    """The chunk shape (S = 256 queries, 4 query heads a K/V head) at an
    offset that is no multiple of the group: the causal edge crosses the
    last group, every earlier group is read whole."""
    S, M = 256, 16
    KB = _group_keys(2, S, 4, M)
    off = KB + 300
    assert off % KB and off + S <= M * 128
    q, kp, vp, pt, lens = _mk_paged(B=1, Hkv=2, M=M, lens=(off + S - 1,))
    got, want = _walk_pair(_mk_ragged_q(1, S, 8, seed=13), kp, vp, (off,), pt,
                           S=S, scale=0.1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_dispatcher_ragged_reasons_and_counter():
    """Dispatch accounting: tile-aligned ragged S hits the kernel
    (llm_attn_kernel_total{path="paged_kernel"}), a query block too big
    for VMEM and a forced-dense override fall back with their reasons."""
    from paddle_tpu.observability import REGISTRY

    from paddle_tpu.ops import decode_attention as da

    fam = REGISTRY.get("llm_attn_kernel_total")

    def counts():
        return {l: c.value for l, c in fam.series()}

    q, kp, vp, pt, lens = _mk_paged()
    qs = _mk_ragged_q(3, 3, 8, seed=8)
    before = counts().get(("paged_kernel", "tile_aligned"), 0.0)
    out = paged_decode_attention(qs, kp, vp, lens, pt, interpret=True)
    assert counts()[("paged_kernel", "tile_aligned")] == before + 1
    want = _paged_dense(qs, kp, vp, lens, pt, None, None, 1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # a ragged block whose S*rep rows of VMEM state cannot fit rides the
    # kernel as sub-blocks of queries (here 2 x 800), each a ragged block
    huge = _mk_ragged_q(3, 1600, 8, seed=9)  # 3200 rows > 6MB state cap
    b = counts().get(("paged_kernel", "query_blocks"), 0.0)
    got = paged_decode_attention(huge, kp, vp, lens, pt, interpret=True)
    assert counts()[("paged_kernel", "query_blocks")] == b + 1
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_paged_dense(
            huge, kp, vp, lens, pt, None, None, 1 / 128 ** 0.5)),
        rtol=2e-5, atol=2e-5)
    # the test/bench override pins the fallback for A/B runs
    b = counts().get(("paged_dense", "forced"), 0.0)
    da._FORCE_PATH = "dense"
    try:
        forced = paged_decode_attention(qs, kp, vp, lens, pt,
                                        interpret=True)
    finally:
        da._FORCE_PATH = None
    assert counts()[("paged_dense", "forced")] == b + 1
    np.testing.assert_allclose(np.asarray(forced), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_dense_gather_cap():
    """The fallback's gather stops at the batch-max logical length when
    offsets are concrete: a short batch in a long-max-pages pool reads
    only the used pages (same numbers either way — the tail it skips is
    causally masked)."""
    from paddle_tpu.ops import decode_attention as da

    q, kp, vp, pt, lens = _mk_paged(M=16, lens=(37, 100, 120))
    seen = []
    orig = da.gather_pages

    def spy(pool, tbl):
        seen.append(tbl.shape[1])
        return orig(pool, tbl)

    da.gather_pages = spy
    try:
        got = _paged_dense(q, kp, vp, lens, pt, None, None, 1 / 128 ** 0.5)
    finally:
        da.gather_pages = orig
    assert seen and all(m == 1 for m in seen)  # 121 tokens -> 1 page of 128
    want = _paged_dense(q, kp, vp, lens, pt[:, :2], None, None,
                        1 / 128 ** 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # traced offsets keep the full-table gather (shape must stay static)
    jitted = jax.jit(lambda o: da._paged_dense(
        q, kp, vp, o, pt, None, None, 1 / 128 ** 0.5))
    np.testing.assert_allclose(np.asarray(jitted(lens)), np.asarray(got),
                               rtol=2e-5, atol=2e-5)
