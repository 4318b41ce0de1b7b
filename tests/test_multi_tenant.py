"""Multi-tenant serving: paged LoRA adapter pool + constrained decoding.

Three layers under test:
- host-side units: the constraint automaton (regex + JSON-schema, sink
  semantics, reject counting), the adapter registry's refcount/LRU
  contract, the adapter-seeded prefix keys, and the router's HBM-aware
  load score;
- engine parity: a batch mixing >= 3 adapters and a schema-constrained
  row decodes bitwise identical to per-request solo generate() (Llama +
  GPT, paged), adapter=None rides the reserved zero page at exactly the
  base-model output, and swapping adapters/constraints after warmup()
  triggers zero recompiles;
- conservation: adapter refcounts balance after EVERY tick under
  interleaved finish / expiry / preemption, including a faults-marker
  case where admission dies mid-flight (mirrors the kv page-pool suite
  in test_prefix_cache.py).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.constrain import (
    compile_constraint, regex_from_schema)
from paddle_tpu.inference.prefix_cache import PrefixCache, prefix_key
from paddle_tpu.inference.router import Router
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, LlamaConfig, LlamaForCausalLM)
from paddle_tpu.models.lora import (
    AdapterRegistry, LoraAdapter, lora_sites)
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.observability.scrape import SampleSet

pytestmark = pytest.mark.quick

V = 1024
EOS = V - 1


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(11)
    cfg = GPTConfig.tiny(max_position_embeddings=256)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def vocab():
    """Toy id -> string map: single digits for ids 0-9 (regex-friendly),
    distinct words elsewhere, </s> at the eos id."""
    v = [str(i) if i < 10 else f"w{i}" for i in range(V)]
    v[EOS] = "</s>"
    return v


def _adapters(model, n, rank=4):
    sites = lora_sites(model)
    return {f"a{i}": LoraAdapter.random(sites, rank=rank, seed=100 + i)
            for i in range(n)}


def _solo(model, prompt, n, **kw):
    """Solo-generate oracle, truncated at eos inclusive — the solo loop
    pads finished rows out to max_new_tokens; the engine stops."""
    ids = paddle.to_tensor(np.asarray(prompt, np.int32)[None, :])
    out = model.generate(ids, max_new_tokens=n, **kw)
    toks = []
    for t in np.asarray(out._value)[0]:
        toks.append(int(t))
        if int(t) == EOS:
            break
    return toks


def _counter(name):
    fam = _obs.snapshot().get(name)
    return sum(s["value"] for s in fam["series"]) if fam else 0.0


# --------------------------------------------------- constraint automaton


def test_regex_automaton_masks_and_forces_eos(vocab):
    tc = compile_constraint(r"[0-9][0-9]", vocab, EOS)
    cur = tc.cursor()
    m = cur.mask()
    assert m[3] and m[7] and not m[20] and not m[EOS]  # digits only, no eos
    assert cur.advance(4)
    assert cur.advance(2)
    m = cur.mask()  # pattern complete: ONLY eos remains
    assert m[EOS] and not m[:10].any() and m.sum() == 1
    assert cur.advance(EOS)
    # sink after eos: still only eos (a wedged grammar can't wedge a slot)
    assert cur.mask()[EOS] and cur.mask().sum() == 1


def test_disallowed_token_sinks_and_counts_reject(vocab):
    tc = compile_constraint(r"[0-9]+", vocab, EOS)
    cur = tc.cursor()
    r0 = _counter("llm_constraint_rejects_total")
    assert not cur.advance(500)  # "w500" is not a digit
    assert cur.rejects == 1
    assert _counter("llm_constraint_rejects_total") == r0 + 1
    assert cur.mask()[EOS] and cur.mask().sum() == 1  # sink


def test_schema_compiles_and_accepts_canonical_json():
    import json

    schema = {"type": "object", "properties": {
        "a": {"type": "integer"}, "ok": {"type": "boolean"}}}
    # char-level vocab: every printable char is its own token
    cvocab = [chr(c) for c in range(0x20, 0x7F)]
    ceos = len(cvocab)
    cvocab.append("</s>")
    tc = compile_constraint(schema, cvocab, ceos)
    cur = tc.cursor()
    text = json.dumps({"a": -42, "ok": True}, separators=(",", ":"))
    for ch in text:
        assert cur.advance(cvocab.index(ch)), (ch, text)
    assert cur.mask()[ceos]  # accepting: eos allowed
    # property order is part of the grammar (declaration-order emission)
    other = {"type": "object", "properties": {
        "ok": {"type": "boolean"}, "a": {"type": "integer"}}}
    assert regex_from_schema(schema) != regex_from_schema(other)


# ------------------------------------------------ adapter pool / registry


def test_registry_refcounts_lru_eviction_and_errors(model):
    reg = AdapterRegistry(model, max_adapters=2, rank=4)
    ads = _adapters(model, 3)
    for aid, ad in ads.items():
        reg.register(aid, ad)
    assert reg.acquire(None) == 0  # reserved zero adapter, never pinned
    with pytest.raises(KeyError):
        reg.acquire("nope")
    pa = reg.acquire("a0")
    pb = reg.acquire("a1")
    assert pa != pb and 0 not in (pa, pb)
    assert reg.acquire("a2") is None  # both pages pinned: exhausted
    reg.release("a0")
    pc = reg.acquire("a2")  # evicts unreferenced a0 (LRU), reuses its page
    assert pc == pa and reg.evictions == 1
    assert reg.page_for("a0") is None  # cold again
    reg.release("a1")
    reg.release("a2")
    with pytest.raises(AssertionError):
        reg.release("a2")  # below zero is loud
    st = reg.stats()
    assert st["pages_pinned"] == 0 and st["loads"] == 3


def test_zero_page_survives_warm_and_writes(model):
    reg = AdapterRegistry(model, max_adapters=2, rank=4)
    reg.register("a0", _adapters(model, 1)["a0"])
    reg.warm()
    reg.acquire("a0")
    for a_pool, b_pool in reg.pool.tree():
        assert not np.asarray(a_pool[0]).any()  # page 0 stays all-zero
        assert not np.asarray(b_pool[0]).any()


# -------------------------------------- adapter-seeded prefix keys (sat 1)


def test_prefix_key_adapter_seed_splits_and_none_keeps_golden():
    p = np.arange(13, dtype=np.int32)
    # None keeps the historical chain bit for bit (golden from
    # test_router.py pins the same digest)
    assert prefix_key(p, 4).hex() \
        == "66fe6dfe4f40fd2dd3cd1e5ccc498cf0eaf59af3"
    assert prefix_key(p, 4, adapter_id=None) == prefix_key(p, 4)
    ka = prefix_key(p, 4, adapter_id="tenant-a")
    kb = prefix_key(p, 4, adapter_id="tenant-b")
    assert ka != kb and ka != prefix_key(p, 4)


def test_prefix_cache_never_crosses_adapters():
    pc = PrefixCache(page_size=4)
    p = np.arange(10, dtype=np.int32)
    pc.insert(p, [5, 6, 7], adapter_id="a")
    assert pc.match(p, adapter_id="a")[0] == 9
    assert pc.match(p, adapter_id="b") == (0, [])  # same tokens, other kv
    assert pc.match(p) == (0, [])                  # base model: no match


# ----------------------------------------------- router hbm score (sat 2)


def test_load_score_hbm_absent_not_zero():
    r = Router([("rep", "127.0.0.1:9")])
    s = SampleSet()
    s.add("llm_queue_depth", {"target": "rep"}, 2.0)
    r._samples = s
    base = r.load_score("rep")
    assert base == 2.0  # no hbm family exported: contributes NOTHING
    s.add("hbm_utilization_ratio", {"target": "rep"}, 0.5)
    assert r.load_score("rep") == base + 4.0 * 0.5


# --------------------------------------------------------- engine parity


def test_engine_mixed_adapters_and_constraint_match_solo(model, vocab):
    ads = _adapters(model, 3)
    reg = AdapterRegistry.from_adapters(model, ads, rank=4)
    eng = LLMEngine(model, max_batch_slots=4, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=16, adapters=reg, constraint_vocab=vocab)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, V, n).astype(np.int32) for n in (12, 7, 19, 9)]
    specs = [("a0", None), ("a1", None), ("a2", r"[0-9]+"), (None, None)]
    futs = [eng.submit(p, max_new_tokens=6, adapter_id=aid, constraint=cst)
            for p, (aid, cst) in zip(prompts, specs)]
    eng.run_until_complete()
    for p, (aid, cst), f in zip(prompts, specs, futs):
        tc = compile_constraint(cst, vocab, EOS) if cst is not None else None
        want = _solo(model, p, 6, eos_token_id=EOS, kv_layout="paged",
                     page_size=32, adapter_id=aid,
                     adapters={aid: ads[aid]} if aid else None,
                     token_mask_fn=tc)
        assert f.result(timeout=1) == want, (aid, cst)
    assert eng.stats()["adapters"]["pages_pinned"] == 0


def test_engine_gpt_adapters_and_constraint_match_solo(gpt_model, vocab):
    ads = _adapters(gpt_model, 3)
    reg = AdapterRegistry.from_adapters(gpt_model, ads, rank=4)
    eng = LLMEngine(gpt_model, max_batch_slots=4, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=16, adapters=reg, constraint_vocab=vocab)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, V, n).astype(np.int32) for n in (10, 15, 8, 6)]
    specs = [("a0", None), ("a1", None), ("a2", None), (None, r"[0-9]+")]
    futs = [eng.submit(p, max_new_tokens=5, adapter_id=aid, constraint=cst)
            for p, (aid, cst) in zip(prompts, specs)]
    eng.run_until_complete()
    for p, (aid, cst), f in zip(prompts, specs, futs):
        tc = compile_constraint(cst, vocab, EOS) if cst is not None else None
        want = _solo(gpt_model, p, 5, eos_token_id=EOS, kv_layout="paged",
                     page_size=32, adapter_id=aid,
                     adapters={aid: ads[aid]} if aid else None,
                     token_mask_fn=tc)
        assert f.result(timeout=1) == want, (aid, cst)


def test_adapter_none_bitwise_matches_plain_engine(model, vocab):
    """adapter=None / constraint=None on a multi-tenant engine is the
    PRE-multi-tenant output: the zero page's delta is exact +0.0 and the
    all-True mask is a sampler no-op."""
    reg = AdapterRegistry.from_adapters(model, _adapters(model, 1), rank=4)
    rng = np.random.RandomState(2)
    p = rng.randint(0, V, 14).astype(np.int32)
    mt = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                   eos_token_id=EOS, kv_layout="paged", page_size=32,
                   prefill_chunk=16, adapters=reg, constraint_vocab=vocab)
    plain = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                      eos_token_id=EOS, kv_layout="paged", page_size=32,
                      prefill_chunk=16)
    got = mt.generate(p, max_new_tokens=6)
    assert got == plain.generate(p, max_new_tokens=6)
    assert got == _solo(model, p, 6, eos_token_id=EOS, kv_layout="paged",
                        page_size=32)


def test_spec_decode_composes_with_adapters(model):
    ads = _adapters(model, 1)
    reg = AdapterRegistry.from_adapters(model, ads, rank=4)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=16, spec_k=2, adapters=reg)
    rng = np.random.RandomState(3)
    p = rng.randint(0, V, 16).astype(np.int32)
    got = eng.generate(p, max_new_tokens=8, adapter_id="a0")
    assert got == _solo(model, p, 8, eos_token_id=EOS, kv_layout="paged",
                        page_size=32, adapter_id="a0", adapters=ads)


def test_zero_recompiles_on_adapter_and_constraint_swap(model, vocab):
    """After warmup() + one primed request, swapping adapters and
    constraints across requests compiles NOTHING: masks and adapter rows
    are device-array values, never program shapes."""
    ads = _adapters(model, 3)
    reg = AdapterRegistry.from_adapters(model, ads, rank=4)
    eng = LLMEngine(model, max_batch_slots=4, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=16, adapters=reg, constraint_vocab=vocab)
    try:
        eng.warmup()
        # the first post-warmup request pays a handful of pre-existing
        # tiny eager-op compiles (host arg building — present on the
        # baseline engine too); prime them before measuring the swaps
        f = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2,
                       adapter_id="a0", constraint=r"[0-9]+")
        eng.run_until_complete()
        f.result(timeout=1)
        r0 = _counter("jit_recompiles_total")
        rng = np.random.RandomState(4)
        for aid, cst in (("a1", None), ("a2", r"[0-9]+"), (None, None),
                         ("a0", {"type": "integer"})):
            f = eng.submit(rng.randint(0, V, 9).astype(np.int32),
                           max_new_tokens=3, adapter_id=aid, constraint=cst)
            eng.run_until_complete()
            f.result(timeout=1)
        assert _counter("jit_recompiles_total") == r0
    finally:
        from paddle_tpu.observability import profiling as _prof

        _prof.mark_warm(False)  # don't leak warm-mode into other tests


def test_constraint_validation_rejects_loudly(model, vocab):
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=16, constraint_vocab=vocab)
    p = np.arange(6, dtype=np.int32)
    r0 = _counter("llm_constraint_rejects_total")
    with pytest.raises(TypeError):
        eng.submit(p, constraint=42)
    assert _counter("llm_constraint_rejects_total") == r0 + 1
    with pytest.raises(ValueError):  # adapters not configured
        eng.submit(p, adapter_id="a0")
    bare = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                     eos_token_id=EOS)
    with pytest.raises(ValueError, match="constraint_vocab"):
        bare.submit(p, constraint=r"[0-9]+")  # a wire form needs the vocab


# --------------------------------------------- adapter-pool conservation


def _assert_adapters_balanced(eng):
    """Refcount conservation: every adapter's pin count equals the live
    requests holding its page (slots + the in-flight prefill); queued /
    finished requests hold nothing."""
    reg = eng.adapters
    held = {}
    live = list(eng.slot_req)
    if eng._prefilling is not None:
        live.append(eng._prefilling[0])  # (request, slot, tokens consumed)
    for r in live:
        if r is not None and r.adapter_page:
            held[r.adapter_id] = held.get(r.adapter_id, 0) + 1
            assert reg._page_of.get(r.adapter_id) == r.adapter_page, \
                f"slot holds page {r.adapter_page} but registry moved it"
    for aid, ref in reg._ref.items():
        assert ref == held.get(aid, 0), \
            f"adapter {aid!r}: refcount {ref} != {held.get(aid, 0)} holders"
    assert not (set(held) - set(reg._ref)), "holder of an unloaded adapter"


def test_adapter_pool_conservation_under_churn(model, vocab):
    """Interleaved finish / deadline expiry / pool-dry preemption with
    MORE adapters than registry pages (acquire-exhaustion requeues) and a
    constrained row in the mix: refcounts balance after EVERY tick and
    drain to zero."""
    rng = np.random.RandomState(40)
    t = [0.0]
    reg = AdapterRegistry(model, max_adapters=2, rank=4)
    for aid, ad in _adapters(model, 3).items():
        reg.register(aid, ad)
    eng = LLMEngine(model, max_batch_slots=3, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=16, num_pages=6, clock=lambda: t[0],
                    adapters=reg, constraint_vocab=vocab)
    shared = rng.randint(0, V, 34).astype(np.int32)
    futs = [
        eng.submit(np.concatenate([shared,
                                   rng.randint(0, V, 3).astype(np.int32)]),
                   max_new_tokens=20, adapter_id="a0"),  # preemption fodder
        eng.submit(rng.randint(0, V, 20).astype(np.int32),
                   max_new_tokens=30, timeout=5.0,
                   adapter_id="a1"),                     # expires mid-flight
        eng.submit(np.concatenate([shared,
                                   rng.randint(0, V, 5).astype(np.int32)]),
                   max_new_tokens=3, adapter_id="a2"),   # 3rd adapter: must
                                                         # wait for a page
        eng.submit(rng.randint(0, V, 8).astype(np.int32),
                   max_new_tokens=4, constraint=r"[0-9]+"),
    ]
    for i in range(300):
        if not (eng._pending.qsize() or eng._prefilling is not None
                or any(r is not None for r in eng.slot_req)):
            break
        eng.step()
        _assert_adapters_balanced(eng)
        if i == 8:
            t[0] = 10.0  # fire the deadline mid-decode
    assert all(f.done() for f in futs), "engine did not drain"
    _assert_adapters_balanced(eng)
    assert eng.stats()["adapters"]["pages_pinned"] == 0
    assert eng.stats()["llm_kv_pages_in_use"] == 0


@pytest.mark.faults
def test_admission_death_releases_adapter(model):
    """Admission dying between the adapter acquire and prefill completion
    (poisoned compiled call) fails only that request; the adapter unpins
    and the next request for the SAME adapter admits and matches solo."""
    rng = np.random.RandomState(42)
    ads = _adapters(model, 1)
    reg = AdapterRegistry.from_adapters(model, ads, rank=4)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    eos_token_id=EOS, kv_layout="paged", page_size=32,
                    prefill_chunk=32, adapters=reg)
    real = eng._get_chunk_prefill()
    calls = {"n": 0}

    def poisoned(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected admission fault")
        return real(*args, **kw)

    eng._chunk_jit = poisoned
    f1 = eng.submit(rng.randint(0, V, 40).astype(np.int32),
                    max_new_tokens=4, adapter_id="a0")
    eng.step()
    with pytest.raises(RuntimeError, match="injected admission fault"):
        f1.result(timeout=1)
    _assert_adapters_balanced(eng)
    assert eng.stats()["adapters"]["pages_pinned"] == 0
    p2 = rng.randint(0, V, 12).astype(np.int32)
    got = eng.generate(p2, max_new_tokens=4, adapter_id="a0")
    assert got == _solo(model, p2, 4, eos_token_id=EOS, kv_layout="paged",
                        page_size=32, adapter_id="a0", adapters=ads)
    _assert_adapters_balanced(eng)
