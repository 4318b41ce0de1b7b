"""Hierarchical kv tiers (inference/kv_host_cache.py + engine demote/promote).

Three layers under test:
- the HostKVPool alone (host-side, no engine): LRU + disk spill round trips,
  idempotent staging, checksum quarantine, torn-spill invisibility;
- the engine cycle: greedy decode stays BITWISE identical with the tiers on
  vs off through forced demote -> evict -> promote -> COW-fork cycles
  (Llama bf16 host-only, GPT int8 through the disk tier), promotion restarts
  chunked prefill at the first truly-uncached token, and copies stay batched
  (one gather program ever, pow-2-bucketed uploads);
- conservation: the PR-6 pool invariant extended across all three tiers
  after EVERY tick under demote/finish/expiry/preempt churn, plus the
  faults-marker cases (torn spill, corrupt spill, mid-promotion death) where
  the engine must fall back to re-prefill — corrupt kv is never served.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.kv_host_cache import HostKVPool
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing.faults import FaultyFS, flip_bit

pytestmark = pytest.mark.quick


# ------------------------------------------------------ host pool alone


def _mk_blocks(seed, dtype=np.float32):
    """Two layers of (k, v) host blocks, shaped like one gathered page."""
    rng = np.random.RandomState(seed)
    return [tuple(rng.rand(2, 4, 3).astype(dtype) for _ in range(2))
            for _ in range(2)]


def _blocks_equal(a, b):
    return all(np.array_equal(x, y)
               for la, lb in zip(a, b) for x, y in zip(la, lb))


def test_pool_put_get_lru_and_idempotence():
    pool = HostKVPool(host_pages=2)
    blocks = _mk_blocks(0)
    assert pool.put(b"k1", b"root", 4, None, blocks)
    assert not pool.put(b"k1", b"root", 4, None, _mk_blocks(9))  # idempotent
    assert b"k1" in pool and pool.tier_of(b"k1") == "host"
    e = pool.get(b"k1")
    assert e.ntok == 4 and e.tier == "host" and _blocks_equal(e.blocks, blocks)
    # overflow without a disk tier DROPS the pool's own LRU entry
    pool.put(b"k2", b"k1", 4, None, _mk_blocks(1))
    pool.put(b"k3", b"k2", 4, None, _mk_blocks(2))
    assert b"k1" not in pool and pool.dropped == 1 and len(pool) == 2
    assert pool.host_bytes == sum(
        a.nbytes for e in (pool.get(b"k2"), pool.get(b"k3"))
        for lt in e.blocks for a in lt)


def test_pool_partial_candidates_span_tiers(tmp_path):
    pool = HostKVPool(host_pages=1, disk_dir=str(tmp_path), disk_pages=4)
    toks1 = np.array([5, 6, 7], np.int32)
    toks2 = np.array([5, 9], np.int32)
    pool.put(b"t1", b"p", 3, toks1, _mk_blocks(3))
    pool.put(b"t2", b"p", 2, toks2, _mk_blocks(4))  # spills t1 to disk
    assert pool.tier_of(b"t1") == "disk" and pool.tier_of(b"t2") == "host"
    cands = pool.partial_candidates(b"p")
    assert {k for k, _, _ in cands} == {b"t1", b"t2"}
    got = {k: list(np.asarray(t)) for k, _, t in cands}
    assert got[b"t1"] == [5, 6, 7] and got[b"t2"] == [5, 9]
    pool.discard(b"t1")
    assert b"t1" not in pool
    assert [k for k, _, _ in pool.partial_candidates(b"p")] == [b"t2"]


def test_pool_disk_spill_roundtrip_bf16_bitwise(tmp_path):
    """bf16 (and f32 scale-style) blocks survive the spill byte-exact —
    the property the engine's bitwise decode parity rests on."""
    import jax.numpy as jnp

    bf16 = np.dtype(jnp.bfloat16)
    rng = np.random.RandomState(7)
    blocks = [tuple([rng.rand(2, 4, 3).astype(bf16),
                     rng.rand(2, 4, 3).astype(bf16),
                     rng.rand(2, 4).astype(np.float32),
                     rng.rand(2, 4).astype(np.float32)])]
    pool = HostKVPool(host_pages=1, disk_dir=str(tmp_path), disk_pages=4)
    pool.put(b"a", b"r", 4, None, blocks)
    pool.put(b"b", b"r", 4, None, _mk_blocks(5))  # pushes "a" to disk
    assert pool.tier_of(b"a") == "disk" and pool.demotions_to_disk == 1
    e = pool.get(b"a")
    assert e is not None and e.tier == "disk" and pool.disk_loads == 1
    assert all(x.dtype == y.dtype and x.tobytes() == y.tobytes()
               for x, y in zip(blocks[0], e.blocks[0]))


def test_pool_corrupt_spill_quarantined_on_load(tmp_path):
    pool = HostKVPool(host_pages=1, disk_dir=str(tmp_path), disk_pages=4)
    pool.put(b"a", b"r", 4, None, _mk_blocks(6))
    pool.put(b"b", b"r", 4, None, _mk_blocks(7))
    path = pool._disk[b"a"]["path"]
    flip_bit(path)  # committed-then-decayed media
    assert pool.get(b"a") is None and pool.quarantined == 1
    assert b"a" not in pool  # never retried
    assert os.path.exists(path + ".quarantined") and not os.path.exists(path)


@pytest.mark.faults
def test_pool_torn_spill_is_invisible(tmp_path):
    """A writer killed mid-spill (FaultyFS torn write) leaves NO committed
    file: the entry degrades to a clean miss, not a corrupt hit."""
    pool = HostKVPool(host_pages=1, disk_dir=str(tmp_path), disk_pages=4)
    pool.put(b"a", b"r", 4, None, _mk_blocks(8))
    with FaultyFS(match="*.kvblk*", faults={0: "torn"}) as fs:
        pool.put(b"b", b"r", 4, None, _mk_blocks(9))  # spill of "a" torn
    assert fs.log and fs.log[0][1] == "torn"
    assert b"a" not in pool and pool.dropped == 1
    assert pool.get(b"a") is None
    leftovers = [f for f in os.listdir(tmp_path) if f.endswith(".kvblk")]
    assert leftovers == []  # tmp cleaned up, nothing half-visible


# ------------------------------------------------ engine cycle (parity)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _oracle(model, prompt, n):
    ids = paddle.to_tensor(np.asarray(prompt, np.int32)[None, :])
    out = model.generate(ids, max_new_tokens=n)
    return list(np.asarray(out._value)[0])


def _drain_to_tiers(eng):
    """Force the full demotion cycle: stage every cached page host-side,
    then LRU-evict the HBM copies — the next shared-prefix request can only
    hit by PROMOTING from the lower tiers."""
    while eng.demote_step(force=True):
        pass
    evictable = int(eng._page_cached.sum())
    if evictable:
        assert eng._evict_prefix(evictable)


def _assert_tiers_balanced(eng):
    """PR-6 pool conservation, extended across the host + disk tiers."""
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
    pool = eng._host_kv
    if pool is None:
        return
    st = pool.stats()
    assert st["host_entries"] == len(pool._host) <= pool.host_pages
    assert st["host_bytes"] == sum(
        pool._entry_bytes(e) for e in pool._host.values())
    assert st["disk_entries"] == len(pool._disk) <= max(pool.disk_pages, 0)
    for rec in pool._disk.values():  # catalog only lists COMMITTED spills
        assert os.path.exists(rec["path"])
    for parent, keys in pool._partials.items():
        assert keys, "empty partial-tail bucket left behind"
        for k in keys:
            assert k in pool, "partial index points at a vanished entry"


def test_tier_cycle_bitwise_parity_llama_host(model):
    """Greedy decode is BITWISE identical tiers on vs off through a forced
    demote -> evict -> promote -> COW-fork cycle (bf16, host tier only)."""
    rng = np.random.RandomState(60)
    shared = rng.randint(0, 1024, 44).astype(np.int32)  # off the page grid
    mk = lambda t: np.concatenate(  # noqa: E731
        [shared, rng.randint(0, 1024, t).astype(np.int32)])
    b1, b2 = [mk(4), mk(6)], [mk(3), mk(5)]
    outs = {}
    for on in (True, False):
        kw = {"host_cache_pages": 16} if on else {}
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                        kv_layout="paged", page_size=32, prefill_chunk=16,
                        **kw)
        got, cow0 = [], 0
        for i, batch in enumerate((b1, b2)):
            futs = [eng.submit(p, max_new_tokens=5) for p in batch]
            eng.run_until_complete()
            got.append([f.result(timeout=1) for f in futs])
            if on and i == 0:
                _drain_to_tiers(eng)
                _assert_tiers_balanced(eng)
                cow0 = eng.stats()["prefix_cache"]["cow_copies"]
        outs[on] = got
        if on:
            st = eng.stats()["prefix_cache"]
            tiers = st["tiers"]
            assert tiers["demotions"] > 0 and tiers["promotions"] > 0
            assert tiers["host"]["hit_tokens"] > 0
            assert tiers["host"]["hit_ratio"] > 0
            # batch 2's tails diverge INSIDE the promoted partial-tail
            # page: the first decode write forks it AFTER the promotion
            assert st["cow_copies"] > cow0
            assert eng.stats()["llm_kv_pages_in_use"] == 0
            _assert_tiers_balanced(eng)
    assert outs[True] == outs[False]
    for p, g in zip(b1 + b2, outs[True][0] + outs[True][1]):
        assert g == _oracle(model, p, 5)


def test_tier_cycle_disk_roundtrip_gpt_int8(tmp_path):
    """int8 kv (+ f32 scales) through the DISK tier: a host pool of 2
    pages forces spills, and promotion reads them back byte-exact —
    proven by bitwise decode parity against the tiers-off engine."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(11)
    cfg = GPTConfig.tiny(max_position_embeddings=128)
    gpt = GPTForCausalLM(cfg)
    gpt.eval()
    rng = np.random.RandomState(61)
    shared = rng.randint(0, cfg.vocab_size, 40).astype(np.int32)
    mk = lambda t: np.concatenate(  # noqa: E731
        [shared, rng.randint(0, cfg.vocab_size, t).astype(np.int32)])
    b1, b2 = [mk(4), mk(6)], [mk(3), mk(7)]
    outs = {}
    for on in (True, False):
        kw = {"host_cache_pages": 2,
              "disk_cache_dir": str(tmp_path / "kv"),
              "disk_cache_pages": 16} if on else {}
        eng = LLMEngine(gpt, max_batch_slots=2, max_seq_len=128,
                        kv_layout="paged", page_size=32, prefill_chunk=16,
                        cache_dtype="int8", **kw)
        got = []
        for i, batch in enumerate((b1, b2)):
            futs = [eng.submit(p, max_new_tokens=5) for p in batch]
            eng.run_until_complete()
            got.append([f.result(timeout=1) for f in futs])
            if on and i == 0:
                _drain_to_tiers(eng)
                _assert_tiers_balanced(eng)
        outs[on] = got
        if on:
            tiers = eng.stats()["prefix_cache"]["tiers"]
            assert tiers["spilled_to_disk"] > 0
            assert tiers["disk"]["loads"] > 0
            assert tiers["disk"]["hit_tokens"] > 0
            _assert_tiers_balanced(eng)
    assert outs[True] == outs[False]
    for p, g in zip(b1 + b2, outs[True][0] + outs[True][1]):
        ids = paddle.to_tensor(np.asarray(p, np.int32)[None, :])
        want = list(np.asarray(gpt.generate(ids, max_new_tokens=5)._value)[0])
        assert g == want


def test_promotion_restarts_prefill_at_first_uncached_token(model):
    """After a demote/evict cycle, re-submitting the same prompt promotes
    the staged blocks and prefills in ONE chunk instead of five — the tier
    hit costs a copy, not a re-prefill."""
    from paddle_tpu.observability import metrics as obs

    count = lambda: obs.counter(  # noqa: E731
        "llm_prefill_chunks_total", "x").value
    rng = np.random.RandomState(62)
    p = rng.randint(0, 1024, 40).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=8,
                    host_cache_pages=8)
    n0 = count()
    first = eng.generate(p, max_new_tokens=4)
    assert count() - n0 == 5  # ceil(40 / 8): cold
    _drain_to_tiers(eng)
    n1 = count()
    again = eng.generate(p, max_new_tokens=4)
    # 39 of 40 usable tokens promoted back: one chunk recomputes the last
    assert count() - n1 == 1
    assert again == first == _oracle(model, p, 4)
    _assert_tiers_balanced(eng)


def test_copies_stay_batched_one_program(model):
    """The demotion gather runs ONE fixed-shape compiled program ever
    (padded to demote_batch), and promotion uploads retrace only per pow-2
    bucket — varying entry counts must not compile per-count programs."""
    rng = np.random.RandomState(63)
    shared = rng.randint(0, 1024, 40).astype(np.int32)
    mk = lambda t: np.concatenate(  # noqa: E731
        [shared, rng.randint(0, 1024, t).astype(np.int32)])
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    host_cache_pages=16, demote_batch=4)
    # the compiled-program cache is shared across engines wrapping the same
    # function: count this engine's NEW signatures, not the absolute size
    g0 = eng._get_gather()._cache_size()
    u0 = eng._get_upload()._cache_size()
    for tails in ((4, 6), (3,), (5, 7)):
        for t in tails:
            eng.generate(mk(t), max_new_tokens=3)
        _drain_to_tiers(eng)
    assert eng._gather_jit._cache_size() - g0 == 1
    eng.generate(mk(8), max_new_tokens=3)  # promotes a multi-page chain
    assert eng._upload_jit._cache_size() - u0 <= 2  # pow-2 buckets
    _assert_tiers_balanced(eng)


def test_demotion_stays_off_the_tick_path(model):
    """step() NEVER demotes — staging belongs to the background worker,
    which spawns with the pump and joins on stop()."""
    rng = np.random.RandomState(64)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    host_cache_pages=8)
    calls = []
    orig = eng.demote_step
    eng.demote_step = lambda force=False: (calls.append(force),
                                           orig(force))[1]
    eng.generate(rng.randint(0, 1024, 40).astype(np.int32),
                 max_new_tokens=5)
    assert calls == [], "a tick called demote_step"
    eng.start()
    assert eng._demote_thread is not None and eng._demote_thread.is_alive()
    f = eng.submit(rng.randint(0, 1024, 12).astype(np.int32),
                   max_new_tokens=3)
    f.result(timeout=60)
    eng.stop()
    assert eng._demote_thread is None  # joined and cleared with the pump
    assert all(force is False for force in calls)  # worker polls unforced


def test_tiers_absent_not_zero_and_require_the_prefix_cache(model):
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32)
    assert "tiers" not in eng.stats()["prefix_cache"]  # pre-tier config
    eng2 = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                     kv_layout="paged", page_size=32, prefill_chunk=32,
                     host_cache_pages=4)
    tiers = eng2.stats()["prefix_cache"]["tiers"]
    assert tiers["host"]["capacity"] == 4 and tiers["disk"]["capacity"] == 0
    with pytest.raises(ValueError, match="requires the prefix cache"):
        LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                  host_cache_pages=4, prefix_cache=False)
    # the bare default has the pool and the prefix cache the tiers ride on
    eng3 = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                     host_cache_pages=4)
    assert eng3.stats()["prefix_cache"]["tiers"]["host"]["capacity"] == 4


# -------------------------------------------- conservation + fault churn


def test_tier_conservation_under_churn(model, tmp_path):
    """Interleaved demote / promote / finish / expiry / preemption over a
    pool too small for everyone, with a 3-page host tier spilling to a
    4-page disk tier: the three-tier conservation invariant holds after
    EVERY tick and every staging pass."""
    rng = np.random.RandomState(65)
    t = [0.0]
    eng = LLMEngine(model, max_batch_slots=3, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    num_pages=6, clock=lambda: t[0],
                    host_cache_pages=3, disk_cache_dir=str(tmp_path / "kv"),
                    disk_cache_pages=4)
    shared = rng.randint(0, 1024, 34).astype(np.int32)
    mk = lambda t_: np.concatenate(  # noqa: E731
        [shared, rng.randint(0, 1024, t_).astype(np.int32)])
    futs = [
        eng.submit(mk(3), max_new_tokens=20),          # preemption fodder
        eng.submit(rng.randint(0, 1024, 20).astype(np.int32),
                   max_new_tokens=30, timeout=5.0),    # expires mid-flight
        eng.submit(mk(5), max_new_tokens=3),           # finishes early
    ]
    resubmitted = False
    for i in range(300):
        if not (eng._pending.qsize() or eng._prefilling is not None
                or any(r is not None for r in eng.slot_req)):
            if resubmitted:
                break
            # second wave: evict the (staged) HBM copies so admission goes
            # through the PROMOTE path mid-churn
            eng._evict_prefix(int(eng._page_cached.sum()))
            futs.append(eng.submit(mk(4), max_new_tokens=4))
            resubmitted = True
        eng.step()
        _assert_tiers_balanced(eng)
        if i % 3 == 0:
            eng.demote_step(force=True)
            _assert_tiers_balanced(eng)
        if i == 8:
            t[0] = 10.0  # fire the deadline mid-decode
    done = [f for f in futs if f.done()]
    assert len(done) == 4, "engine did not drain"
    _assert_tiers_balanced(eng)
    assert eng.stats()["llm_kv_pages_in_use"] == 0
    tiers = eng.stats()["prefix_cache"]["tiers"]
    assert tiers["demotions"] > 0 and tiers["promotions"] > 0


@pytest.mark.faults
def test_torn_and_corrupt_spills_fall_back_to_reprefill(model, tmp_path):
    """A torn disk spill vanishes whole (clean miss) and a corrupt
    committed spill quarantines on load: both degrade to re-prefill with
    BITWISE-identical output — corrupt kv is never served."""
    rng = np.random.RandomState(66)
    disk = tmp_path / "kv"
    shared = rng.randint(0, 1024, 40).astype(np.int32)
    mk = lambda t: np.concatenate(  # noqa: E731
        [shared, rng.randint(0, 1024, t).astype(np.int32)])
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    host_cache_pages=1, disk_cache_dir=str(disk),
                    disk_cache_pages=8)
    p1, p2 = mk(4), mk(6)
    a1 = eng.generate(p1, max_new_tokens=4)
    a2 = eng.generate(p2, max_new_tokens=4)
    # staging 3 entries through a 1-page host tier spills twice; the FIRST
    # spill is torn mid-write (the process "dies")
    with FaultyFS(match="*.kvblk*", faults={0: "torn"}) as fs:
        _drain_to_tiers(eng)
    assert fs.log and fs.log[0][1] == "torn"
    pool = eng._host_kv
    assert pool.dropped >= 1  # the torn spill degraded to a clean miss
    assert not list(disk.glob("*.tmp")), "torn tmp file left behind"
    _assert_tiers_balanced(eng)
    # the torn entry reads as a plain miss, so the NEXT staging pass
    # re-demoted it: all 3 entries end up staged, 2 committed to disk
    committed = sorted(disk.glob("*.kvblk"))
    assert len(committed) == 2 and pool.stats()["disk_entries"] == 2
    flip_bit(str(committed[0]))  # committed-then-decayed media
    g1 = eng.generate(p1, max_new_tokens=4)
    g2 = eng.generate(p2, max_new_tokens=4)
    assert g1 == a1 == _oracle(model, p1, 4)
    assert g2 == a2 == _oracle(model, p2, 4)
    assert pool.quarantined >= 1
    assert list(disk.glob("*.quarantined")), "corrupt spill not quarantined"
    _assert_tiers_balanced(eng)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


@pytest.mark.faults
def test_mid_promotion_death_restores_free_pages(model):
    """An upload that dies mid-promotion (injected stand-in for an OOM /
    consumed-donation failure) gives its freshly popped pages back and
    escalates; the healed engine then serves the same prefix exactly."""
    rng = np.random.RandomState(67)
    shared = rng.randint(0, 1024, 40).astype(np.int32)
    mk = lambda t: np.concatenate(  # noqa: E731
        [shared, rng.randint(0, 1024, t).astype(np.int32)])
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    host_cache_pages=8)
    eng.generate(mk(4), max_new_tokens=3)
    _drain_to_tiers(eng)
    free0 = sorted(eng._free_pages)

    def poisoned(caches, pages, blocks):
        raise RuntimeError("injected upload fault")

    eng._upload_jit = poisoned
    eng.submit(mk(5), max_new_tokens=3)
    with pytest.raises(RuntimeError, match="injected upload fault"):
        eng.step()
    assert sorted(eng._free_pages) == free0, "promotion leaked pages"
    _assert_tiers_balanced(eng)
    eng._upload_jit = None  # heal: the staged entries are still intact
    p3 = mk(6)
    assert eng.generate(p3, max_new_tokens=3) == _oracle(model, p3, 3)
    assert eng.stats()["prefix_cache"]["tiers"]["promotions"] > 0
    _assert_tiers_balanced(eng)
