"""kv-cache layout edges (models/kv_cache.py): int8 roundtrip tolerance,
static scatter at the buffer edges, paged scatter across page boundaries,
and page-table reuse after reclaim."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.models.kv_cache import (
    TRASH_PAGE, _paged_scatter, _paged_scatter_scale, _quantize_kv,
    _scatter, _to_head_major, pages_for, update_paged_cache,
    update_paged_quant_cache)
from paddle_tpu.ops.decode_attention import gather_pages

pytestmark = pytest.mark.quick


def test_int8_roundtrip_tolerance():
    """Dequantized values stay within half a quantization step of the
    original — absmax/254 per (head, token) row."""
    rng = np.random.RandomState(0)
    kv = jnp.asarray(rng.randn(2, 4, 16, 32).astype(np.float32) * 3.0)
    q, scale = _quantize_kv(kv)
    assert q.dtype == jnp.int8 and scale.shape == (2, 4, 16)
    deq = q.astype(jnp.float32) * scale[..., None]
    absmax = jnp.max(jnp.abs(kv), axis=-1, keepdims=True)
    err = np.asarray(jnp.abs(deq - kv))
    bound = np.asarray(absmax / 254.0) + 1e-6
    assert (err <= bound).all()


def test_int8_roundtrip_zero_row():
    """An all-zero row must survive (scale floor, no 0/0)."""
    kv = jnp.zeros((1, 2, 3, 8), jnp.float32)
    q, scale = _quantize_kv(kv)
    assert np.asarray(q).sum() == 0
    assert np.isfinite(np.asarray(scale)).all()


@pytest.mark.parametrize("offset", [0, 13, 15])  # first, middle, LAST row
def test_static_scatter_edges(offset):
    rng = np.random.RandomState(1)
    buf = jnp.zeros((2, 3, 16, 8), jnp.float32)
    new = jnp.asarray(rng.randn(2, 3, 1, 8).astype(np.float32))
    out = np.asarray(_scatter(buf, new, offset))
    np.testing.assert_array_equal(out[:, :, offset], np.asarray(new)[:, :, 0])
    mask = np.ones(16, bool)
    mask[offset] = False
    assert np.abs(out[:, :, mask]).max() == 0.0


def test_static_scatter_per_slot_vector_offsets():
    rng = np.random.RandomState(2)
    buf = jnp.zeros((3, 2, 16, 8), jnp.float32)
    new = jnp.asarray(rng.randn(3, 2, 1, 8).astype(np.float32))
    offs = jnp.asarray([0, 7, 15], jnp.int32)
    out = np.asarray(_scatter(buf, new, offs))
    for b, o in enumerate([0, 7, 15]):
        np.testing.assert_array_equal(out[b, :, o], np.asarray(new)[b, :, 0])


def _mk_pool(P=7, H=2, ps=8, D=16):
    return jnp.zeros((P, H, ps, D), jnp.float32)


def test_paged_scatter_offset0_lastrow_and_page_boundary():
    """Writes at position 0, at the last row of a page, and a span CROSSING
    a page boundary all land where gather_pages expects them."""
    rng = np.random.RandomState(3)
    ps, M = 8, 3
    pool = _mk_pool()
    pt = jnp.asarray([[1, 2, 3], [4, 5, TRASH_PAGE]], jnp.int32)
    # span of 4 tokens starting at ps-2 = 6: rows 6,7 of page0 + 0,1 of page1
    new = jnp.asarray(rng.randn(2, 2, 4, 16).astype(np.float32))
    pos = jnp.asarray([0, ps - 2], jnp.int32)
    out = _paged_scatter(pool, new, pos, pt)
    full = np.asarray(gather_pages(out, pt))  # [B, H, M*ps, D]
    for s in range(4):
        np.testing.assert_array_equal(full[0, :, 0 + s],
                                      np.asarray(new)[0, :, s])
        np.testing.assert_array_equal(full[1, :, ps - 2 + s],
                                      np.asarray(new)[1, :, s])
    # last row of slot 0's LAST page
    last = jnp.asarray(rng.randn(2, 2, 1, 16).astype(np.float32))
    out2 = _paged_scatter(out, last, jnp.asarray([M * ps - 1, 0], jnp.int32),
                          pt)
    full2 = np.asarray(gather_pages(out2, pt))
    np.testing.assert_array_equal(full2[0, :, M * ps - 1],
                                  np.asarray(last)[0, :, 0])


def test_paged_scatter_clips_past_table_to_trash():
    """Positions beyond the page table's coverage (padded prefill tails)
    must land in the trash page, not in another slot's pages."""
    rng = np.random.RandomState(4)
    ps = 8
    pool = _mk_pool()
    pt = jnp.asarray([[1, TRASH_PAGE, TRASH_PAGE],
                      [2, 3, 4]], jnp.int32)
    new = jnp.asarray(rng.randn(2, 2, 2, 16).astype(np.float32))
    # slot 0 writes at rows 30, 31 — far past its single allocated page
    out = _paged_scatter(pool, new, jnp.asarray([30, 0], jnp.int32), pt)
    # slot 1's pages (2, 3, 4) hold ONLY its own write
    assert np.abs(np.asarray(out[3:5])).max() == 0.0
    np.testing.assert_array_equal(
        np.asarray(out[2, :, 0]), np.asarray(new)[1, :, 0])
    # the garbage went to the trash page
    assert np.abs(np.asarray(out[TRASH_PAGE])).max() > 0.0


def test_paged_scatter_overflow_past_full_table_goes_to_trash():
    """A padded prefill tail overflowing the WHOLE table (every entry a
    real page) must land in the trash page — clipping to the last entry
    would clobber the slot's own last page."""
    rng = np.random.RandomState(7)
    ps, M = 8, 3
    pool = _mk_pool()
    pt = jnp.asarray([[1, 2, 3]], jnp.int32)  # fully populated table
    real = jnp.asarray(rng.randn(1, 2, 1, 16).astype(np.float32))
    out = _paged_scatter(pool, real, jnp.asarray([M * ps - 1], jnp.int32), pt)
    # garbage span starting right past the table's coverage
    junk = jnp.asarray(rng.randn(1, 2, 4, 16).astype(np.float32))
    out = _paged_scatter(out, junk, jnp.asarray([M * ps], jnp.int32), pt)
    full = np.asarray(gather_pages(out, pt))
    np.testing.assert_array_equal(full[0, :, M * ps - 1],
                                  np.asarray(real)[0, :, 0])  # survived
    assert np.abs(full[0, :, :M * ps - 1]).max() == 0.0
    assert np.abs(np.asarray(out[TRASH_PAGE])).max() > 0.0


def test_paged_quant_scatter_scales():
    rng = np.random.RandomState(5)
    ps = 8
    pool = jnp.zeros((5, 2, ps, 16), jnp.int8)
    spool = jnp.full((5, 2, ps), 1e-8, jnp.float32)
    pt = jnp.asarray([[1, 2]], jnp.int32)
    k = jnp.asarray(rng.randn(1, 3, 2, 16).astype(np.float32))  # [B,S,H,D]
    cache = (pool, pool, jnp.asarray(6, jnp.int32), pt, spool, spool)
    new_cache, kq, vq, ks, vs = update_paged_quant_cache(cache, k, k, 6)
    kq, ks = kq._value, ks._value  # helpers return autograd-wrapped Tensors
    # rows 6..8 cross the page boundary; dequantized gather matches input
    full = np.asarray(gather_pages(kq, pt)).astype(np.float32) \
        * np.asarray(gather_pages(ks, pt))[..., None]
    hm = np.asarray(_to_head_major(jnp.asarray(k)))
    for s in range(3):
        np.testing.assert_allclose(full[0, :, 6 + s], hm[0, :, s],
                                   rtol=2e-2, atol=2e-2)


def test_page_table_reuse_after_reclaim():
    """Free a slot's pages, hand the SAME physical pages to a new slot in a
    different order: reads through the new table see only the new data."""
    rng = np.random.RandomState(6)
    ps = 8
    pool = _mk_pool()
    pt_a = jnp.asarray([[1, 2, 3]], jnp.int32)
    a = jnp.asarray(rng.randn(1, 2, 20, 16).astype(np.float32))
    cache = (pool, pool, jnp.asarray(0, jnp.int32), pt_a)
    (k1, _, _, _), _, _ = update_paged_cache(
        cache, jnp.transpose(a, (0, 2, 1, 3)), jnp.transpose(a, (0, 2, 1, 3)),
        0)
    k1 = k1._value
    # reclaim: same pages reused by a new request, permuted table
    pt_b = jnp.asarray([[3, 1, 2]], jnp.int32)
    b = jnp.asarray(rng.randn(1, 2, 17, 16).astype(np.float32))
    cache_b = (k1, k1, jnp.asarray(0, jnp.int32), pt_b)
    (k2, _, _, _), _, _ = update_paged_cache(
        cache_b, jnp.transpose(b, (0, 2, 1, 3)), jnp.transpose(b, (0, 2, 1, 3)),
        0)
    full = np.asarray(gather_pages(k2._value, pt_b))
    np.testing.assert_array_equal(full[0, :, :17], np.asarray(b)[0])


def test_pages_for():
    assert pages_for(0, 8) == 0
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2


@pytest.mark.parametrize("off,C,ps,M", [
    (0, 32, 16, 6),     # page-aligned: two whole pages, the third untouched
    (5, 32, 16, 6),     # a partial first page, a whole one, a partial last
    (16, 16, 16, 6),    # one whole page
    (3, 8, 16, 6),      # inside one page
    (70, 32, 16, 6),    # runs past the table's coverage: the tail to trash
    (96, 32, 16, 6),    # wholly past it
])
def test_chunk_page_write_equals_the_row_scatter(off, C, ps, M):
    """A slot's run of C tokens written a page block at a time leaves every
    page the row scatter leaves (the trash page aside: what lands there is
    never read), in a k/v pool and in a scale pool."""
    from paddle_tpu.models.kv_cache import (
        _chunk_pages, _scatter_rows, _token_pages_rows, _write_chunk_pages)

    rng = np.random.RandomState(off + C)
    P, H, D = 9, 2, 8
    page_row = jnp.asarray([[3, 7, 1, 5, TRASH_PAGE, TRASH_PAGE]], jnp.int32)[:, :M]
    for pool, vals in (
            (rng.randn(P, H, ps, D), rng.randn(C, H, D)),   # k/v rows
            (rng.rand(P, H, ps), rng.rand(C, H))):          # scales
        pool = jnp.asarray(pool, jnp.float32)
        vals = jnp.asarray(vals, jnp.float32)
        page, row = _token_pages_rows(jnp.asarray([off]), page_row, C, ps, M)
        want = np.asarray(_scatter_rows(pool, vals[None], page, row))
        got = np.asarray(_write_chunk_pages(
            pool, vals, _chunk_pages(jnp.asarray([off]), page_row, C, ps)))
        live = np.arange(P) != TRASH_PAGE
        assert np.array_equal(got[live], want[live])
