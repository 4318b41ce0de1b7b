"""Prefix cache (inference/prefix_cache.py) + refcounted page allocator.

Two layers under test:
- the radix index alone (host-side, no engine): chained-hash matching,
  longest-common-prefix partial tails, LRU leaf eviction, steal-back;
- the engine's refcounted allocator invariants: pool conservation and
  no double-free/double-decref under interleaved finish / expiry /
  preemption, plus a faults-marker case where admission dies mid-flight
  and the pool still balances.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.quick


# ------------------------------------------------------- radix index alone


def test_match_empty_and_insert_roundtrip():
    pc = PrefixCache(page_size=4)
    prompt = np.arange(10, dtype=np.int32)
    assert pc.match(prompt) == (0, [])
    # pages 0..2 of some slot: two full blocks + a 2-token tail
    new = pc.insert(prompt, [7, 8, 9])
    assert new == [7, 8, 9] and len(pc) == 3
    matched, pages = pc.match(prompt)
    # capped at n-1 = 9 usable tokens: 2 full blocks + 1 of the tail's 2
    assert matched == 9 and pages == [7, 8, 9]


def test_match_is_chained_not_positional():
    """Block hashes commit to the whole prefix: the same block content
    under a DIFFERENT first block must not match."""
    pc = PrefixCache(page_size=4)
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    pc.insert(a, [5, 6, 7])
    b = a.copy()
    b[0] = 99  # same second block, different first
    matched, pages = pc.match(b)
    assert matched == 0 and pages == []


def test_partial_tail_longest_common_prefix():
    """A partial tail matches its LONGEST shared prefix, not all-or-
    nothing — the shared-system-prompt case where prompts diverge inside
    the tail page."""
    pc = PrefixCache(page_size=4)
    a = np.array([1, 2, 3, 4, 10, 11, 12], np.int32)  # tail [10, 11, 12]
    pc.insert(a, [5, 6])
    b = np.array([1, 2, 3, 4, 10, 11, 99, 50], np.int32)  # diverges at 12
    matched, pages = pc.match(b)
    assert matched == 6 and pages == [5, 6]  # full block + 2 tail tokens
    # a second cached tail with a longer overlap wins
    c = np.array([1, 2, 3, 4, 10, 11, 99], np.int32)
    pc.insert(c, [5, 9])  # full block already cached; new tail page 9
    matched, pages = pc.match(b)
    assert matched == 7 and pages == [5, 9]


def test_duplicate_insert_holds_nothing_new():
    pc = PrefixCache(page_size=4)
    p = np.arange(6, dtype=np.int32)
    assert pc.insert(p, [3, 4]) == [3, 4]
    # a second slot prefilled the same prompt privately: index unchanged
    assert pc.insert(p, [8, 9]) == []
    assert sorted(pc.pages()) == [3, 4]


def test_lru_evicts_leaves_first_in_touch_order():
    pc = PrefixCache(page_size=4)
    a = np.arange(8, dtype=np.int32)           # blocks A0, A1
    b = np.array([9, 9, 9, 9, 1, 2, 3], np.int32)  # block B0 + tail
    pc.insert(a, [3, 4])
    pc.insert(b, [5, 6])
    # touch A's WHOLE chain (one extra token so block A1 is matchable
    # under the n-1 cap): B is now least recently used
    pc.match(np.concatenate([a, [99]]).astype(np.int32))
    evictable = lambda p: True  # noqa: E731
    # evict_one returns (key, tokens, page, ntok) — the demotion path's
    # identity handoff (PR-19); page order is what LRU policy pins here
    key, tokens, page, ntok = pc.evict_one(evictable)
    assert page == 6 and ntok == 3          # B's tail (leaf) first
    assert tokens.tolist() == [1, 2, 3] and isinstance(key, bytes)
    key, tokens, page, ntok = pc.evict_one(evictable)
    assert page == 5 and tokens is None and ntok == 4  # then B0 (now a leaf)
    # A0 has a child (A1): only A1 is a leaf
    assert pc.evict_one(evictable)[2] == 4
    assert pc.evict_one(evictable)[2] == 3
    assert pc.evict_one(evictable) is None and len(pc) == 0


def test_evict_one_respects_predicate():
    pc = PrefixCache(page_size=4)
    pc.insert(np.arange(4, dtype=np.int32), [3])
    assert pc.evict_one(lambda p: False) is None
    assert pc.evict_one(lambda p: p == 3)[2] == 3


def test_freeable_count_pins_ancestors_of_live_pages():
    """A page mapped by a live slot pins its whole chain: eviction can
    never free those nodes, and the engine must know that BEFORE it starts
    destroying warm entries for a doomed allocation."""
    pc = PrefixCache(page_size=4)
    pc.insert(np.arange(10, dtype=np.int32), [3, 4, 5])   # chain of 3
    pc.insert(np.array([9, 9, 9, 9], np.int32), [6])      # separate block
    assert pc.freeable_count(lambda p: False) == 4
    # page 5 (the tail leaf) in use -> its ancestors 4 and 3 pin too
    assert pc.freeable_count(lambda p: p == 5) == 1
    # only the separate block's page in use -> the chain stays freeable
    assert pc.freeable_count(lambda p: p == 6) == 3


def test_evict_page_steal_back():
    pc = PrefixCache(page_size=4)
    pc.insert(np.arange(6, dtype=np.int32), [3, 4])
    key, tokens, page, ntok = pc.evict_page(4)   # the tail leaf
    assert page == 4 and ntok == 2 and tokens.tolist() == [4, 5]
    assert pc.evict_page(4) is None              # already gone
    assert pc.evict_page(3)[2] == 3              # now a leaf itself


# -------------------------------------------- allocator invariants (engine)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _assert_pool_balanced(eng):
    """Every page is EITHER free with refcount 0 OR held, and the refcount
    equals slot holds + cache holds exactly — the conservation invariant
    behind 'decref instead of free'."""
    P = eng.num_pages
    free = list(eng._free_pages)
    assert len(free) == len(set(free)), "duplicate page in the free list"
    holds = {}
    for pages in eng._slot_pages:
        for p in pages:
            holds[p] = holds.get(p, 0) + 1
    cached = set()
    if eng._prefix is not None:
        cached = set(eng._prefix.pages())
        assert len(cached) == len(eng._prefix.pages()), \
            "two cache nodes hold one page"
    assert {p for p in range(P) if eng._page_cached[p]} == cached
    assert 0 not in free and int(eng._page_ref[0]) == 0  # trash page
    for p in range(1, P):
        ref = int(eng._page_ref[p])
        assert ref == holds.get(p, 0) + (1 if p in cached else 0), \
            f"page {p}: refcount {ref} out of balance"
        assert (p in free) == (ref == 0), f"page {p}: free-list mismatch"


def test_pool_conservation_under_finish_expiry_preempt(model):
    """Interleaved finish / deadline expiry / pool-dry preemption over a
    pool too small for everyone: the refcounted allocator never leaks or
    double-frees a page (checked after EVERY tick)."""
    rng = np.random.RandomState(40)
    t = [0.0]
    eng = LLMEngine(model, max_batch_slots=3, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    num_pages=6, clock=lambda: t[0])
    shared = rng.randint(0, 1024, 34).astype(np.int32)
    futs = [
        eng.submit(np.concatenate([shared,
                                   rng.randint(0, 1024, 3).astype(np.int32)]),
                   max_new_tokens=20),          # long: preemption fodder
        eng.submit(rng.randint(0, 1024, 20).astype(np.int32),
                   max_new_tokens=30, timeout=5.0),  # expires mid-flight
        eng.submit(np.concatenate([shared,
                                   rng.randint(0, 1024, 5).astype(np.int32)]),
                   max_new_tokens=3),           # finishes early, shares
    ]
    for i in range(200):
        if not (eng._pending.qsize() or eng._prefilling is not None
                or any(r is not None for r in eng.slot_req)):
            break
        eng.step()
        _assert_pool_balanced(eng)
        if i == 8:
            t[0] = 10.0  # fire the deadline mid-decode
    done = [f for f in futs if f.done()]
    assert len(done) == 3, "engine did not drain"
    _assert_pool_balanced(eng)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


def test_decref_below_zero_is_loud(model):
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32)
    with pytest.raises(AssertionError):
        eng._decref(1)  # page 1 is free: refcount 0


def test_release_pages_is_idempotent(model):
    rng = np.random.RandomState(41)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32)
    eng.submit(rng.randint(0, 1024, 10).astype(np.int32), max_new_tokens=4)
    eng.step()
    assert eng._slot_pages[0]
    held = list(eng._slot_pages[0])
    eng._release_pages(0)
    eng._release_pages(0)  # second call must be a no-op, not a double-free
    for p in held:
        assert int(eng._page_ref[p]) in (0, 1)  # 1 when the cache holds it
    _assert_pool_balanced(eng)
    eng.slot_req[0] = None
    eng._prefilling = None
    eng._drain_queue(RuntimeError("test cleanup"))


@pytest.mark.faults
def test_admission_dies_mid_alloc_pool_balances(model):
    """Admission that dies between taking pages and finishing its prefill
    (a poisoned compiled call — the injected stand-in for an OOM or a
    compile failure) fails ONLY that request; its pages decref back and
    the pool balances, so the next request admits normally."""
    rng = np.random.RandomState(42)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32)
    real = eng._get_chunk_prefill()
    calls = {"n": 0}

    def poisoned(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 1:  # call-count keyed, like testing/faults.py
            raise RuntimeError("injected admission fault")
        return real(*args, **kw)

    eng._chunk_jit = poisoned
    f1 = eng.submit(rng.randint(0, 1024, 40).astype(np.int32),
                    max_new_tokens=4)
    eng.step()
    with pytest.raises(RuntimeError, match="injected admission fault"):
        f1.result(timeout=1)
    _assert_pool_balanced(eng)
    assert eng.stats()["llm_kv_pages_in_use"] == 0
    p2 = rng.randint(0, 1024, 12).astype(np.int32)
    got = eng.generate(p2, max_new_tokens=4)
    ids = paddle.to_tensor(np.asarray(p2, np.int32)[None, :])
    want = list(np.asarray(model.generate(ids, max_new_tokens=4)._value)[0])
    assert got == want
    _assert_pool_balanced(eng)
