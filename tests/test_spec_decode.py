"""Speculative decoding (models/spec_decode, ops/sampling, the solo
generate spec path, and the engine's verify-and-rollback tick).

Oracles, all deterministic on CPU:

- greedy spec-on output must be BITWISE identical to spec-off on every
  cache layout (dense / paged / int8 solo, paged / int8 pages in the
  engine, Llama and GPT) —
  the verify ladder's argmaxes ARE the single-step tokens;
- an oracle drafter that feeds the verify pass the true continuation
  pins the acceptance accounting (every draft accepted, fewer verify
  calls than tokens); a garbage drafter pins rollback (tokens rejected,
  pages trimmed, output still exact);
- the fused sampler's top_k=1 sampled rows reproduce greedy bitwise.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import (
    GPTConfig,
    GPTForCausalLM,
    LlamaConfig,
    LlamaForCausalLM,
)
from paddle_tpu.models.spec_decode import NGramDrafter, get_drafter
from paddle_tpu.ops.sampling import mask_logits, sample_rows, spec_accept
from paddle_tpu.observability import tracing

pytestmark = pytest.mark.quick


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
    paddle.seed(9)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


def _oracle(model, prompt, n):
    ids = paddle.to_tensor(np.asarray(prompt, np.int32)[None, :])
    out = model.generate(ids, max_new_tokens=n)
    return list(np.asarray(out._value)[0])


class OracleDrafter:
    """Drafts the TRUE greedy continuation (precomputed solo) — every
    draft the verify pass sees is correct, so acceptance is maximal."""

    name = "oracle"

    def __init__(self, full_seq):
        self.seq = np.asarray(full_seq, np.int32)

    def propose(self, context, k):
        i = len(np.asarray(context).reshape(-1))
        out = np.zeros(int(k), np.int32)
        tail = self.seq[i:i + int(k)]
        out[:tail.size] = tail
        return out


class BadDrafter:
    """Constant-garbage drafts: (almost) everything gets rejected, so
    every verify rolls back K tokens — rollback accounting's worst case."""

    name = "bad"

    def propose(self, context, k):
        return np.zeros(int(k), np.int32)


# ---------------------------------------------------------------- drafters
def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    # suffix trigram [1,2,3] recurs at the start: drafts = what followed it
    ctx = np.array([1, 2, 3, 4, 5, 1, 2, 3], np.int32)
    assert d.propose(ctx, 3).tolist() == [4, 5, 1]
    # no recurrence anywhere: deterministic repeat-last filler
    assert d.propose(np.array([1, 2, 3], np.int32), 4).tolist() == [3, 3, 3, 3]
    # short continuation after the hit pads by repeating the last draft
    ctx = np.array([7, 8, 9, 7, 8], np.int32)  # [7,8] recurs, only 9 follows
    assert d.propose(ctx, 3).tolist() == [9, 7, 8][:3]
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=0)


def test_get_drafter_resolution(model):
    assert isinstance(get_drafter(None), NGramDrafter)
    assert isinstance(get_drafter("ngram"), NGramDrafter)
    own = BadDrafter()
    assert get_drafter(own) is own
    assert get_drafter(model).model is model  # wrapped DraftModelDrafter
    with pytest.raises(ValueError):
        get_drafter(42)


# ------------------------------------------------------------ fused sampler
def test_mask_logits_topk_topp_semantics():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(3, 32).astype(np.float32))
    ones = jnp.ones((3,), jnp.float32)
    # top_k=4 keeps exactly the 4 largest (random floats: no ties)
    m = mask_logits(logits, ones, jnp.full((3,), 4, jnp.int32), ones)
    assert (np.isfinite(np.asarray(m)).sum(-1) == 4).all()
    # k=0 and k>=V disable; top_p=1.0 disables: everything stays finite
    for k in (0, 32, 99):
        m = mask_logits(logits, ones, jnp.full((3,), k, jnp.int32), ones)
        assert np.isfinite(np.asarray(m)).all()
    # top_p -> 0 keeps only the argmax
    m = mask_logits(logits, ones, jnp.zeros((3,), jnp.int32),
                    jnp.full((3,), 1e-9, jnp.float32))
    keep = np.asarray(np.isfinite(np.asarray(m)))
    assert (keep.sum(-1) == 1).all()
    assert (keep.argmax(-1) == np.asarray(logits).argmax(-1)).all()


def test_sample_rows_topk1_and_greedy_match_argmax():
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(4, 64).astype(np.float32))
    want = np.asarray(logits).argmax(-1)
    key = jax.random.PRNGKey(3)
    greedy = sample_rows(logits, key, jnp.zeros((4,), bool),
                         jnp.ones((4,), jnp.float32),
                         jnp.zeros((4,), jnp.int32),
                         jnp.ones((4,), jnp.float32))
    assert (np.asarray(greedy) == want).all()
    # sampled with top_k=1: the mask leaves one candidate — bitwise greedy
    k1 = sample_rows(logits, key, jnp.ones((4,), bool),
                     jnp.full((4,), 0.7, jnp.float32),
                     jnp.ones((4,), jnp.int32),
                     jnp.ones((4,), jnp.float32))
    assert (np.asarray(k1) == want).all()


def test_sample_rows_matches_scalar_select_per_row():
    """Per-row knob arrays reproduce generation._select's scalar-knob
    outputs row for row (same key): the broadcast path is the same math."""
    from paddle_tpu.models.generation import _select

    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(3, 48).astype(np.float32))
    key = jax.random.PRNGKey(11)
    for k, p, t in ((0, 1.0, 1.0), (5, 1.0, 0.8), (3, 0.6, 1.3)):
        rows = sample_rows(logits, key, jnp.ones((3,), bool),
                           jnp.full((3,), t, jnp.float32),
                           jnp.full((3,), k, jnp.int32),
                           jnp.full((3,), p, jnp.float32))
        ref = _select(logits, key, True, t, k, p)
        assert (np.asarray(rows) == np.asarray(ref)[:, 0]).all()


def test_spec_accept_greedy_prefix_semantics():
    rng = np.random.RandomState(3)
    B, K, V = 2, 3, 16
    lad = rng.randint(0, V, (B, K + 1)).astype(np.int32)
    logits = np.full((B, K + 1, V), -5.0, np.float32)
    for b in range(B):
        for i in range(K + 1):
            logits[b, i, lad[b, i]] = 5.0
    drafts = lad[:, :K].copy()
    drafts[1, 0] = (drafts[1, 0] + 1) % V  # row 1 diverges immediately
    out, n = spec_accept(
        jnp.asarray(logits), jnp.asarray(drafts), jax.random.PRNGKey(0),
        jnp.zeros((B,), bool), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32))
    out, n = np.asarray(out), np.asarray(n)
    assert n[0] == K and (out[0] == lad[0]).all()  # full accept + bonus
    assert n[1] == 0 and out[1, 0] == lad[1, 0]    # instant correction


def test_spec_accept_sampled_rejection():
    """Near-one-hot target: correct one-hot drafts are always accepted,
    wrong ones always rejected with the correction drawn off the peak."""
    B, K, V = 2, 2, 8
    peak = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    logits = np.full((B, K + 1, V), -50.0, np.float32)
    for b in range(B):
        for i in range(K + 1):
            logits[b, i, peak[b, i]] = 50.0
    drafts = peak[:, :K].copy()
    drafts[1] = (drafts[1] + 1) % V  # row 1: hopeless drafts
    out, n = spec_accept(
        jnp.asarray(logits), jnp.asarray(drafts), jax.random.PRNGKey(5),
        jnp.ones((B,), bool), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32))
    out, n = np.asarray(out), np.asarray(n)
    assert n[0] == K and (out[0] == peak[0]).all()
    assert n[1] == 0 and out[1, 0] == peak[1, 0]


# ------------------------------------------------------------- solo parity
@pytest.mark.parametrize("cache_dtype,kv_layout", [
    (None, None), (None, "paged"), ("int8", None), ("int8", "paged")])
def test_solo_spec_greedy_bitwise_parity(model, cache_dtype, kv_layout):
    rng = np.random.RandomState(10)
    base_ids = rng.randint(0, 1024, (2, 9)).astype(np.int32)
    # repeat a chunk so the n-gram drafter actually lands some accepts
    ids = np.concatenate([base_ids, base_ids[:, :5]], axis=1)
    kw = dict(max_new_tokens=10, cache_dtype=cache_dtype,
              kv_layout=kv_layout, page_size=128)
    ref = np.asarray(model.generate(ids, **kw)._value)
    got = np.asarray(model.generate(ids, spec_k=4, **kw)._value)
    assert (got == ref).all(), (got, ref)


def test_solo_spec_gpt_and_eos(gpt_model):
    rng = np.random.RandomState(11)
    ids = rng.randint(0, 1024, (2, 8)).astype(np.int32)
    ref = np.asarray(gpt_model.generate(ids, max_new_tokens=8)._value)
    got = np.asarray(gpt_model.generate(ids, max_new_tokens=8,
                                        spec_k=3)._value)
    assert (got == ref).all()
    # early eos pads the rest of the row identically on both paths
    eos = int(ref[0, 2])
    ref_e = np.asarray(gpt_model.generate(
        ids, max_new_tokens=8, eos_token_id=eos, pad_token_id=0)._value)
    got_e = np.asarray(gpt_model.generate(
        ids, max_new_tokens=8, eos_token_id=eos, pad_token_id=0,
        spec_k=3)._value)
    assert (got_e == ref_e).all()


def test_solo_spec_sampled_deterministic_and_valid(model):
    rng = np.random.RandomState(12)
    ids = rng.randint(0, 1024, (2, 10)).astype(np.int32)
    kw = dict(max_new_tokens=6, do_sample=True, temperature=0.9, top_k=8,
              top_p=0.95, spec_k=3)
    paddle.seed(301)
    a = np.asarray(model.generate(ids, **kw)._value)
    paddle.seed(301)
    b = np.asarray(model.generate(ids, **kw)._value)
    assert (a == b).all()                       # same seed, same stream
    assert a.shape == (2, 6) and (a >= 0).all() and (a < 1024).all()
    with pytest.raises(ValueError):
        model.generate(ids, max_new_tokens=4, spec_k=-1)


# ------------------------------------------------------------ engine parity
def test_engine_spec_paged_parity_and_stats(model):
    """Staggered greedy requests through the paged spec tick match their
    solo oracles bitwise; the acceptance accounting is populated."""
    rng = np.random.RandomState(20)
    prompts = [rng.randint(0, 1024, n).astype(np.int32) for n in (6, 13, 21)]
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    spec_k=4)
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_complete()
    for p, f in zip(prompts, futs):
        assert f.result(timeout=1) == _oracle(model, p, 6)
    spec = eng.stats()["spec"]
    assert spec["k"] == 4 and spec["drafter"] == "ngram"
    assert spec["verify_calls"] > 0 and spec["drafted_tokens"] > 0
    assert spec["drafted_tokens"] == (spec["accepted_tokens"]
                                      + spec["rolled_back_tokens"])
    assert 0.0 <= spec["acceptance_ratio"] <= 1.0
    # FIFO control for the cache-aware satellite: a default engine
    # (cache_aware_admission off) never admits out of order
    assert eng.stats()["admission_reorders"] == 0


def test_engine_spec_default_and_int8_parity(model):
    """The bare default engine and int8 pages under the spec tick."""
    rng = np.random.RandomState(21)
    p = rng.randint(0, 1024, 11).astype(np.int32)
    want = _oracle(model, p, 5)
    for kw in (dict(), dict(cache_dtype="int8")):
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                        spec_k=3, **kw)
        assert eng.generate(p, max_new_tokens=5) == want, kw


def test_engine_spec_oracle_drafter_acceptance(model):
    """A drafter that proposes the true continuation makes every verify
    accept its whole draft: max_new tokens in far fewer verify calls —
    the mechanism behind the speedup, pinned deterministically.  The
    same run's trace must carry the spec/draft/verify span triplet on
    its coalesced decode window."""
    rng = np.random.RandomState(22)
    p = rng.randint(0, 1024, 10).astype(np.int32)
    n, k = 12, 3
    seq = np.concatenate([p, np.asarray(_oracle(model, p, n), np.int32)])
    tracer = tracing.Tracer(store=tracing.TraceStore(capacity=8,
                                                     sample_every=1))
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    spec_k=k, spec_draft=OracleDrafter(seq), tracer=tracer)
    assert eng.generate(p, max_new_tokens=n) == list(seq[len(p):])
    spec = eng.stats()["spec"]
    # n-1 decode tokens in ceil((n-1)/(k+1)) verifies instead of n-1 steps
    assert spec["verify_calls"] <= (n - 1 + k) // (k + 1) + 1
    assert spec["verify_calls"] < n - 1
    assert spec["acceptance_ratio"] > 0.5
    assert spec["accepted_tokens"] >= (n - 1) - spec["verify_calls"]
    t = tracer.store.get_trace(tracer.store.list()[0]["trace_id"])
    spec_spans = t.find_spans("spec")
    assert spec_spans and spec_spans[0].attrs["drafted"] > 0
    assert spec_spans[0].attrs["accepted"] > 0
    assert t.find_spans("draft")[0].attrs["tokens"] > 0
    ver = t.find_spans("verify")[0]
    assert ver.attrs["accepted_len"] > 1.0  # oracle drafts: >1 tok/verify


def test_engine_spec_rollback_frees_pages(model):
    """Garbage drafts: every verify rolls back; pages grown for the
    speculative headroom are trimmed back and the output stays exact."""
    rng = np.random.RandomState(23)
    p = rng.randint(0, 1024, 30).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    spec_k=4, spec_draft=BadDrafter())
    assert eng.generate(p, max_new_tokens=6) == _oracle(model, p, 6)
    spec = eng.stats()["spec"]
    assert spec["rolled_back_tokens"] > 0
    assert spec["rolled_back_pages"] > 0   # the 30->35 headroom page, back
    assert spec["acceptance_ratio"] < 0.5
    assert eng.stats()["llm_kv_pages_in_use"] == 0


def test_engine_spec_per_slot_topk1_matches_greedy(model):
    """Per-request top_k rides the fused sampler: a top_k=1 sampled
    request is bitwise greedy, both in plain decode and under spec."""
    rng = np.random.RandomState(24)
    p = rng.randint(0, 1024, 12).astype(np.int32)
    want = _oracle(model, p, 5)
    for spec_k in (0, 3):
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                        spec_k=spec_k)
        f1 = eng.submit(p, max_new_tokens=5, do_sample=True, top_k=1,
                        temperature=0.7)
        f2 = eng.submit(p, max_new_tokens=5)  # greedy slotmate
        eng.run_until_complete()
        assert f1.result(timeout=1) == want, spec_k
        assert f2.result(timeout=1) == want, spec_k


def test_engine_spec_guards(model):
    with pytest.raises(ValueError):
        LLMEngine(model, spec_k=-1)
    with pytest.raises(ValueError):
        LLMEngine(model, spec_k=2, decode_chunk=2)
    with pytest.raises(ValueError):
        LLMEngine(model, cache_aware_admission=True,
                  prefix_cache=False)  # the reorder key is the cached prefix


# ------------------------------------------------- preemption under spec
@pytest.mark.faults
def test_engine_spec_mid_verify_preemption_requeues(model):
    """Two spec slots whose speculative headroom cannot coexist in a tiny
    pool: the loser preempt-requeues mid-verify (recompute path), BOTH
    finish bitwise-exact, and the pool drains to zero.

    The loser is preempted TWICE here (after its first and its second
    token).  Until PR 29 each requeue appended every generated token to the
    prompt, so the second one resumed from prompt + [t0, t0, t1] and the
    sixth token came out 831, third in the reference's ranking (0.11 under
    368: a wrong context, not a near-tie)."""
    rng = np.random.RandomState(26)
    pa = rng.randint(0, 1024, 30).astype(np.int32)
    pb = rng.randint(0, 1024, 30).astype(np.int32)
    tracer = tracing.Tracer(store=tracing.TraceStore(capacity=16,
                                                     sample_every=1))
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=4, prefix_cache=False, spec_k=3,
                    tracer=tracer)  # 3 allocatable pages for 2x(30+spec)
    fa = eng.submit(pa, max_new_tokens=6)
    fb = eng.submit(pb, max_new_tokens=6)
    eng.run_until_complete()
    assert fa.result(timeout=1) == _oracle(model, pa, 6)
    assert fb.result(timeout=1) == _oracle(model, pb, 6)
    assert eng.stats()["llm_kv_pages_in_use"] == 0
    pre = [s for s in tracer.store.list()
           if s["sampled_reason"] == "preempted"]
    assert pre, "expected a page_pool_dry preempt-requeue"
    t = tracer.store.get_trace(pre[0]["trace_id"])
    adm = t.find_spans("admission")
    assert adm[-1].attrs["requeue_reason"] == "page_pool_dry"
    assert t.root.attrs["preempt_requeues"] == 2
    # the regrown prompt holds each generated token once
    assert adm[-1].attrs["prompt_tokens"] == 30 + 2


@pytest.mark.faults
def test_twice_preempted_request_regrows_its_prompt_once(model):
    """The plain decode tick's side of the same rule: a request preempted,
    re-admitted and preempted again resumes from its prompt plus each token
    it generated ONCE, and ends on generate()'s tokens.  B (4 pages in the
    end, the whole pool) loses its third page to A and, re-admitted, its
    fourth to C."""
    rng = np.random.RandomState(29)
    pb, pa, pc = (rng.randint(0, 1024, n).astype(np.int32)
                  for n in (14, 20, 3))
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128, page_size=16,
                    prefill_chunk=32, num_pages=5, prefix_cache=False)
    seen = []
    real = eng._preempt_slot

    def spy(slot, origin="decode"):
        req = eng.slot_req[slot]
        real(slot, origin=origin)
        seen.append((req, req.prompt.copy(), list(req.tokens)))

    eng._preempt_slot = spy
    fb = eng.submit(pb, max_new_tokens=45)
    fa = fc = None
    for _ in range(200):
        if fb.done() and fc is not None and fc.done():
            break
        eng.step()
        b_pos = [int(eng.slot_pos[i]) for i, r in enumerate(eng.slot_req)
                 if r is not None and r.future is fb]
        if fa is None and b_pos and b_pos[0] >= 24:
            fa = eng.submit(pa, max_new_tokens=11)   # holds 2 pages to 31
        if fc is None and len(seen) == 1 and b_pos and b_pos[0] >= 40:
            fc = eng.submit(pc, max_new_tokens=20)   # holds B's 4th page
    assert fb.result(timeout=1) == _oracle(model, pb, 45)
    assert fa.result(timeout=1) == _oracle(model, pa, 11)
    assert fc.result(timeout=1) == _oracle(model, pc, 20)
    assert [r.future is fb for r, _, _ in seen] == [True, True]
    for _, prompt, toks in seen:
        assert list(prompt) == list(pb) + toks
    assert eng.stats()["llm_kv_pages_in_use"] == 0


# --------------------------------------------------- cache-aware admission
def test_cache_aware_admission_reorders_warm_request(model):
    """With one slot busy and a cold + a cache-warm request queued, the
    warm one (longest cached prefix) is admitted first — exactly one
    out-of-FIFO admission — and every result stays exact.  (The FIFO
    control — a default engine never reorders — is asserted on the
    spec-tick engine in test_engine_spec_paged_parity_and_stats.)"""
    rng = np.random.RandomState(27)
    head = rng.randint(0, 1024, 32).astype(np.int32)
    warm0 = np.concatenate([head, rng.randint(0, 1024, 6).astype(np.int32)])
    cold = rng.randint(0, 1024, 28).astype(np.int32)
    warm1 = np.concatenate([head, rng.randint(0, 1024, 4).astype(np.int32)])
    blocker = rng.randint(0, 1024, 12).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    cache_aware_admission=True)
    f0 = eng.submit(warm0, max_new_tokens=2)   # warms the prefix cache
    eng.run_until_complete()
    fbl = eng.submit(blocker, max_new_tokens=4)
    eng.step()                                  # blocker takes the slot
    fc = eng.submit(cold, max_new_tokens=3)     # FIFO head
    fw = eng.submit(warm1, max_new_tokens=3)    # cache hit behind it
    eng.run_until_complete()
    for f, p, n in ((f0, warm0, 2), (fbl, blocker, 4), (fc, cold, 3),
                    (fw, warm1, 3)):
        assert f.result(timeout=1) == _oracle(model, p, n)
    assert eng.stats()["admission_reorders"] == 1
