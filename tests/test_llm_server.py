"""Continuous-batching LLM engine (inference/llm_server.py).

Oracle: per-request greedy tokens must MATCH model.generate run alone (its
dense static cache is the reference) — slots at different depths share one
compiled decode step, a padded final prefill chunk is exact for causal
attention, and eos frees slots mid-flight."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _oracle(model, prompt, n, **kw):
    ids = paddle.to_tensor(np.asarray(prompt, np.int32)[None, :])
    out = model.generate(ids, max_new_tokens=n, **kw)
    return list(np.asarray(out._value)[0])


@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(11)
    gpt = GPTForCausalLM(GPTConfig.tiny(max_position_embeddings=128))
    gpt.eval()
    return gpt


def test_dense_layout_is_refused_with_the_pointer(model):
    """There is one engine.  kv_layout="dense" names the layout that was
    removed and is refused with where its cache lives on; a model without
    the paged surface is told what it lacks."""
    with pytest.raises(ValueError, match=r"removed.*generate\(\)"):
        LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                  kv_layout="dense")
    with pytest.raises(ValueError, match="None or 'paged'"):
        LLMEngine(model, kv_layout="ragged")

    class NoPagedSurface:
        config = model.config
        generate_step = model.generate_step

    with pytest.raises(ValueError, match="lacks _supports_paged_cache, "
                                         "prefill_chunk_step"):
        LLMEngine(NoPagedSurface())


@pytest.mark.parametrize("family", ["llama", "gpt"])
@pytest.mark.parametrize("kv_layout", [None, "paged"])
def test_bare_default_is_the_paged_engine(model, gpt_model, kv_layout,
                                          family):
    """LLMEngine(model) is what the cells run: generate()'s tokens from the
    page pool at full reserved-row capacity, chunks of 128, the prefix
    cache on — and kv_layout="paged" says nothing more."""
    m = model if family == "llama" else gpt_model
    rng = np.random.RandomState(40)
    prompts = [rng.randint(0, m.config.vocab_size, n).astype(np.int32)
               for n in (5, 33, 17)]
    kw = {} if kv_layout is None else dict(kv_layout=kv_layout)
    eng = LLMEngine(m, max_batch_slots=2, max_seq_len=128, **kw)
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_complete()
    for p, f in zip(prompts, futs):
        assert f.result(timeout=1) == _oracle(m, p, 5)
    st = eng.stats()
    assert st["kv_layout"] == "paged"
    assert st["kv_pages_total"] == 2 * (128 // 128)  # slots x L / page_size
    assert eng.prefill_chunk == 128 and st["prefix_cache"] is not None
    assert st["llm_kv_pages_in_use"] == 0  # the pool drains


def test_single_request_matches_generate(model):
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 1024, 12).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128)
    got = eng.generate(prompt, max_new_tokens=6)
    assert got == _oracle(model, prompt, 6)


def test_continuous_batching_parity_and_slot_reuse(model):
    """More requests than slots, different prompt lengths: every request
    still matches its solo-generate oracle."""
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 1024, n).astype(np.int32)
               for n in (5, 17, 33, 9, 26)]
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128)
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_complete()
    for p, f in zip(prompts, futs):
        assert f.result(timeout=1) == _oracle(model, p, 5)


def test_staggered_admission_mid_decode(model):
    """A request admitted while another is mid-decode (slots at different
    positions in the same compiled step) stays exact."""
    rng = np.random.RandomState(2)
    p1 = rng.randint(0, 1024, 20).astype(np.int32)
    p2 = rng.randint(0, 1024, 7).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128)
    f1 = eng.submit(p1, max_new_tokens=8)
    eng.step()  # admit p1 + decode 1 token
    eng.step()
    f2 = eng.submit(p2, max_new_tokens=4)  # joins mid-flight
    eng.run_until_complete()
    assert f1.result(timeout=1) == _oracle(model, p1, 8)
    assert f2.result(timeout=1) == _oracle(model, p2, 4)


def test_eos_frees_slot_early(model):
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 1024, 10).astype(np.int32)
    base = _oracle(model, prompt, 8)
    eos = base[2]  # force an early stop at the 3rd generated token
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    eos_token_id=eos)
    got = eng.generate(prompt, max_new_tokens=8)
    assert got == base[:3]
    assert eng.slot_req == [None]  # slot freed


def test_int8_cache_engine_runs(model):
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, 1024, 12).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    cache_dtype="int8")
    got = eng.generate(prompt, max_new_tokens=4)
    assert len(got) == 4 and all(isinstance(t, int) for t in got)


def test_background_thread_mode(model):
    rng = np.random.RandomState(5)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128).start()
    try:
        futs = [eng.submit(rng.randint(0, 1024, 8).astype(np.int32),
                           max_new_tokens=3) for _ in range(3)]
        outs = [f.result(timeout=120) for f in futs]
        assert all(len(o) == 3 for o in outs)
    finally:
        eng.stop()


def test_per_request_sampling_knobs(model):
    """Slots with different sampling settings share one compiled step: a
    near-zero-temperature sampled request reproduces greedy while a greedy
    request runs alongside; high-temperature sampling actually varies."""
    rng = np.random.RandomState(6)
    p1 = rng.randint(0, 1024, 10).astype(np.int32)
    p2 = rng.randint(0, 1024, 14).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128)
    f1 = eng.submit(p1, max_new_tokens=5, do_sample=True,
                    temperature=1e-4)   # ~greedy
    f2 = eng.submit(p2, max_new_tokens=5)  # greedy slotmate
    eng.run_until_complete()
    assert f1.result(timeout=1) == _oracle(model, p1, 5)
    assert f2.result(timeout=1) == _oracle(model, p2, 5)

    # high temperature + nucleus: two runs should (overwhelmingly) differ
    paddle.seed(101)
    a = eng.generate(p1, max_new_tokens=12, do_sample=True, temperature=5.0,
                     top_p=0.99)
    paddle.seed(202)
    b = eng.generate(p1, max_new_tokens=12, do_sample=True, temperature=5.0,
                     top_p=0.99)
    assert len(a) == len(b) == 12
    assert a != b  # 1024-way vocab at T=5: collision of 12 draws ~ never


def _sampler_series():
    from paddle_tpu import observability as obs

    fam = obs.snapshot().get("llm_sampler_ticks_total")
    return {s["labels"]["path"]: s["value"] for s in fam["series"]}


def test_sampler_counter_follows_the_knobs_of_rows_that_draw(model):
    """stats()["sampler"] classifies each tick by the predicate the compiled
    sampler branches on: greedy traffic never reaches the sort (not even
    with a stale top_p on a greedy request), a temperature-only request
    draws without one, and a top_p request sorts on exactly the ticks it
    decodes.  The registry's family counts the same ticks, and none of it
    compiles a program after warmup()."""
    rng = np.random.RandomState(16)
    p = [rng.randint(0, 1024, n).astype(np.int32) for n in (10, 14, 7)]
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    page_size=32, prefill_chunk=16)
    eng.warmup()
    s0, r0, quiet = eng.stats()["sampler"], _sampler_series(), _compiles()
    assert s0 == {"ticks": 0, "sampled_ticks": 0, "threshold_ticks": 0}

    def served(**knobs):
        before = eng.stats()["sampler"]
        futs = [eng.submit(p[0], max_new_tokens=6, **knobs),
                eng.submit(p[1], max_new_tokens=4)]  # a greedy slotmate
        eng.run_until_complete()
        assert [len(f.result(timeout=1)) for f in futs] == [6, 4]
        after = eng.stats()["sampler"]
        return {k: after[k] - before[k] for k in after}

    # the first token is the host's; 5 decode ticks serve the longer row
    assert served() == {
        "ticks": 5, "sampled_ticks": 0, "threshold_ticks": 0}
    assert served(top_p=0.9, top_k=5) == {       # greedy, stale knobs
        "ticks": 5, "sampled_ticks": 0, "threshold_ticks": 0}
    assert served(do_sample=True, temperature=1.5) == {
        "ticks": 5, "sampled_ticks": 5, "threshold_ticks": 0}
    assert served(do_sample=True, top_k=1024) == {   # k = V cuts nothing
        "ticks": 5, "sampled_ticks": 5, "threshold_ticks": 0}
    assert served(do_sample=True, top_p=0.9) == {
        "ticks": 5, "sampled_ticks": 5, "threshold_ticks": 5}
    # a sampled row of 3 tokens beside a greedy row of 6: 2 ticks sort
    f = [eng.submit(p[0], max_new_tokens=6),
         eng.submit(p[2], max_new_tokens=3, do_sample=True, top_k=8)]
    eng.run_until_complete()
    assert [len(x.result(timeout=1)) for x in f] == [6, 3]
    s1, r1 = eng.stats()["sampler"], _sampler_series()
    assert s1 == {"ticks": 30, "sampled_ticks": 17, "threshold_ticks": 7}
    assert {k: r1[k] - r0.get(k, 0) for k in r1} == {
        "greedy": 13, "draw": 10, "threshold": 7}
    assert _compiles() == quiet


def test_chunked_decode_matches_per_token(model):
    """decode_chunk=4 (multi-step scheduling: 4 tokens per compiled call)
    produces the same greedy outputs, including eos mid-chunk with the
    surplus discarded."""
    rng = np.random.RandomState(8)
    p1 = rng.randint(0, 1024, 11).astype(np.int32)
    p2 = rng.randint(0, 1024, 23).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    decode_chunk=4)
    f1 = eng.submit(p1, max_new_tokens=10)
    f2 = eng.submit(p2, max_new_tokens=7)  # finishes mid-chunk
    eng.run_until_complete()
    assert f1.result(timeout=1) == _oracle(model, p1, 10)
    assert f2.result(timeout=1) == _oracle(model, p2, 7)

    # eos mid-chunk
    base = _oracle(model, p1, 10)
    eos = base[4]  # stops inside the second chunk of 4
    eng2 = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                     decode_chunk=4, eos_token_id=eos)
    got = eng2.generate(p1, max_new_tokens=10)
    assert got == base[:5]


# ------------------------------- paged kv cache + chunked prefill engine


def _prefill_chunk_count():
    from paddle_tpu.observability import metrics as obs

    return obs.counter("llm_prefill_chunks_total", "x").value


def test_paged_engine_parity_mixed_lengths_and_slot_reuse(model):
    """Paged decode + chunked prefill is numerically generate()'s dense
    static cache under mixed prompt lengths, more requests than slots
    (page/slot reuse), and
    chunk boundaries that split prompts."""
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, 1024, n).astype(np.int32)
               for n in (5, 17, 33, 9, 26)]
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16)
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_complete()
    for p, f in zip(prompts, futs):
        assert f.result(timeout=1) == _oracle(model, p, 5)
    st = eng.stats()
    assert st["kv_layout"] == "paged"
    assert st["llm_kv_pages_in_use"] == 0  # everything reclaimed
    assert st["kv_pages_total"] == 2 * (128 // 32)


def test_paged_chunked_prefill_matches_whole_prompt(model):
    """Chunked prefill emits BITWISE the same greedy tokens as the
    solo-generate oracle's whole-prompt prefill, for a prompt spanning
    several chunks including a ragged final chunk."""
    rng = np.random.RandomState(22)
    p = rng.randint(0, 1024, 43).astype(np.int32)  # 6 chunks of 8, ragged
    paged = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                      kv_layout="paged", page_size=32, prefill_chunk=8)
    n0 = _prefill_chunk_count()
    got = paged.generate(p, max_new_tokens=6)
    assert _prefill_chunk_count() - n0 == 6  # ceil(43 / 8)
    assert got == _oracle(model, p, 6)


def test_paged_prefill_tail_overflow_near_capacity(model):
    """A prompt near max_seq_len whose final padded chunk overflows the
    page table's coverage: the tail must spill to the trash page, not wrap
    onto the slot's own last page (regression for the clip-vs-trash bug)."""
    rng = np.random.RandomState(31)
    p = rng.randint(0, 1024, 120).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=96)
    # chunk 2 spans positions 96..191 — 120..127 pad inside L, 128..191 past
    # the whole table
    assert eng.generate(p, max_new_tokens=5) == _oracle(model, p, 5)


def test_paged_int8_matches_dense_int8_engine(model):
    """int8 pages against the dense static int8 cache of generate()."""
    rng = np.random.RandomState(23)
    p = rng.randint(0, 1024, 19).astype(np.int32)
    paged = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                      kv_layout="paged", page_size=32, prefill_chunk=16,
                      cache_dtype="int8")
    assert paged.generate(p, max_new_tokens=4) == \
        _oracle(model, p, 4, cache_dtype="int8")


def test_paged_decode_chunk_crosses_page_boundaries(model):
    """decode_chunk=4 with page_size=32: a single compiled call writes
    tokens across a page boundary; pages grow ahead of the chunk."""
    rng = np.random.RandomState(32)
    p = rng.randint(0, 1024, 29).astype(np.int32)  # decode crosses row 32
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    decode_chunk=4)
    assert eng.generate(p, max_new_tokens=10) == _oracle(model, p, 10)


def test_paged_long_prompt_does_not_stall_decode(model):
    """A long prompt admitted mid-decode prefills one chunk per tick while
    the running slot gets a token EVERY tick — the head-of-line fix,
    asserted through the chunked-prefill counter.  The pump books a tick's
    token one tick later (one decode program stays in flight), so the
    running slot's progress is what it has booked plus what it has in
    flight."""
    rng = np.random.RandomState(24)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=8)
    fa = eng.submit(rng.randint(0, 1024, 6).astype(np.int32),
                    max_new_tokens=40)
    eng.step()  # admit A: its chunk's program goes out
    eng.step()  # ... and is read: A has its first token
    pb = rng.randint(0, 1024, 33).astype(np.int32)  # 5 chunks of 8
    fb = eng.submit(pb, max_new_tokens=4)
    n0 = _prefill_chunk_count()
    progress = lambda: len(eng.slot_req[0].tokens) + int(eng._ahead[0])  # noqa: E731
    for k in range(5):  # the whole admission of B
        before, booked = progress(), len(eng.slot_req[0].tokens)
        eng.step()
        assert progress() == before + 1  # A never stalls
        # and what a tick dispatched is booked by the next one
        assert len(eng.slot_req[0].tokens) == booked + (k > 0)
    assert _prefill_chunk_count() - n0 == 5
    eng.run_until_complete()
    assert fb.result(timeout=1) == _oracle(model, pb, 4)


def test_paged_admission_waits_for_free_pages(model):
    """Pool sized so both requests cannot hold their full contexts at once:
    admission/preemption is by free pages and BOTH still finish with exact
    parity (recompute-style preemption replays the generated prefix)."""
    rng = np.random.RandomState(25)
    pa = rng.randint(0, 1024, 30).astype(np.int32)
    pb = rng.randint(0, 1024, 30).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=3)  # trash + 2 allocatable
    fa = eng.submit(pa, max_new_tokens=4)
    fb = eng.submit(pb, max_new_tokens=4)
    eng.run_until_complete()
    assert fa.result(timeout=1) == _oracle(model, pa, 4)
    assert fb.result(timeout=1) == _oracle(model, pb, 4)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


def test_paged_impossible_request_is_shed(model):
    """A request that can never fit in the whole pool fails with
    ServerOverloadedError instead of preempt-looping forever."""
    from paddle_tpu.inference import ServerOverloadedError

    rng = np.random.RandomState(26)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=2)  # ONE allocatable page = 32 tokens
    f = eng.submit(rng.randint(0, 1024, 20).astype(np.int32),
                   max_new_tokens=60)
    eng.run_until_complete()
    with pytest.raises(ServerOverloadedError):
        f.result(timeout=1)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


def test_paged_deadline_expiry_reclaims_pages(model):
    from paddle_tpu.inference import DeadlineExceededError

    rng = np.random.RandomState(27)
    t = [0.0]
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    clock=lambda: t[0])
    f = eng.submit(rng.randint(0, 1024, 10).astype(np.int32),
                   max_new_tokens=50, timeout=5.0)
    eng.step()
    eng.step()
    assert eng.stats()["llm_kv_pages_in_use"] > 0
    t[0] = 10.0
    eng.step()
    with pytest.raises(DeadlineExceededError):
        f.result(timeout=1)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_warmup_precompiles_exactly_the_tick_programs(model, spec_k,
                                                      cache_dtype):
    """warmup() builds the one chunk program, the COW copy, the decode step
    and (with spec_k) the verify step — one of each, no program a prompt
    length and nothing the first request still has to build."""
    from paddle_tpu.observability import profiling as prof

    rng = np.random.RandomState(28)
    p = rng.randint(0, 1024, 12).astype(np.int32)
    want = _oracle(model, p, 5, cache_dtype=cache_dtype)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128, page_size=32,
                    prefill_chunk=16, spec_k=spec_k, cache_dtype=cache_dtype)
    before = _compile_families()
    try:
        assert eng.warmup() > 0.0
        built = {k: v - before.get(k, 0)
                 for k, v in _compile_families().items() if k != "backend"}
        want_built = {"chunk_prefill": 1, "cow_copy": 1, "decode": 1}
        if spec_k:
            want_built["verify"] = 1
        # and nothing else: no "prefill" / "slot_writer" a prompt length
        assert {k: v for k, v in built.items() if v} == want_built
        assert eng._chunk_jit is not None and list(eng._decode_jit) == [1]
        quiet = _compiles()
        assert eng.generate(p, max_new_tokens=5) == want
        assert _compiles() == quiet
    finally:
        prof.mark_warm(False)


def test_warmup_requires_idle_engine(model):
    rng = np.random.RandomState(29)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32)
    eng.submit(rng.randint(0, 1024, 8).astype(np.int32), max_new_tokens=20)
    eng.step()
    with pytest.raises(RuntimeError):
        eng.warmup()
    eng.run_until_complete()


def test_paged_engine_with_gpt_family(gpt_model):
    gpt = gpt_model
    rng = np.random.RandomState(30)
    p = rng.randint(0, gpt.config.vocab_size, 21).astype(np.int32)
    eng = LLMEngine(gpt, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16)
    assert eng.generate(p, max_new_tokens=6) == _oracle(gpt, p, 6)


# ----------------------------- prefix cache: refcounted shared kv pages


def _mk_shared_prompts(rng, shared_len, tails, vocab=1024):
    shared = rng.randint(0, vocab, shared_len).astype(np.int32)
    return [np.concatenate([shared, rng.randint(0, vocab, t)
                            .astype(np.int32)]) for t in tails]


def test_prefix_cache_bitwise_parity_on_vs_off(model):
    """Greedy decode is BITWISE identical with the prefix cache on vs off,
    across a shared-prefix batch whose tails diverge INSIDE the partial
    tail page (so hits, partial-tail matches, and COW forks all fire)."""
    rng = np.random.RandomState(50)
    prompts = _mk_shared_prompts(rng, 44, (4, 6, 3, 5))  # off the page grid
    outs = []
    for on in (True, False):
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                        kv_layout="paged", page_size=32, prefill_chunk=16,
                        prefix_cache=on)
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_complete()
        outs.append([f.result(timeout=1) for f in futs])
        if on:
            st = eng.stats()["prefix_cache"]
            assert st["hit_tokens"] > 0 and st["cow_copies"] > 0
            assert eng.stats()["llm_kv_pages_in_use"] == 0
    assert outs[0] == outs[1]
    for p, got in zip(prompts, outs[0]):
        assert got == _oracle(model, p, 6)


def test_prefix_cache_parity_int8_paged(model):
    rng = np.random.RandomState(51)
    prompts = _mk_shared_prompts(rng, 40, (5, 7))
    outs = []
    for on in (True, False):
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                        kv_layout="paged", page_size=32, prefill_chunk=16,
                        cache_dtype="int8", prefix_cache=on)
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_complete()
        outs.append([f.result(timeout=1) for f in futs])
    assert outs[0] == outs[1]


def test_prefix_cache_parity_gpt_family(gpt_model):
    gpt = gpt_model
    rng = np.random.RandomState(52)
    prompts = _mk_shared_prompts(rng, 37, (6, 4),
                                 vocab=gpt.config.vocab_size)
    outs = []
    for on in (True, False):
        eng = LLMEngine(gpt, max_batch_slots=2, max_seq_len=128,
                        kv_layout="paged", page_size=32, prefill_chunk=16,
                        prefix_cache=on)
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_complete()
        outs.append([f.result(timeout=1) for f in futs])
    assert outs[0] == outs[1]
    for p, got in zip(prompts, outs[0]):
        assert got == _oracle(gpt, p, 5)


def test_prefix_hit_skips_prefill_chunks(model):
    """A hit starts chunked prefill at the first UNCACHED token: an
    identical re-submitted prompt prefills in ONE chunk instead of five."""
    rng = np.random.RandomState(53)
    p = rng.randint(0, 1024, 40).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=8)
    n0 = _prefill_chunk_count()
    first = eng.generate(p, max_new_tokens=4)
    assert _prefill_chunk_count() - n0 == 5  # ceil(40 / 8): cold
    n1 = _prefill_chunk_count()
    again = eng.generate(p, max_new_tokens=4)
    # 39 of 40 tokens cached (the last one must be recomputed for logits)
    assert _prefill_chunk_count() - n1 == 1
    assert again == first == _oracle(model, p, 4)
    st = eng.stats()["prefix_cache"]
    assert st["hit_tokens"] == 39 and st["prompt_tokens"] == 80


def test_prefix_sharing_multiplies_concurrency_at_fixed_pool(model):
    """The capacity lever: four shared-prefix requests run CONCURRENTLY in
    a pool where unshared paged admission fits only two — admission charges
    only the unique pages."""
    rng = np.random.RandomState(54)
    prompts = _mk_shared_prompts(rng, 96, (7, 7, 7, 7))  # page-aligned share
    peak = {True: 0, False: 0}
    for on in (True, False):
        eng = LLMEngine(model, max_batch_slots=4, max_seq_len=128,
                        kv_layout="paged", page_size=32, prefill_chunk=32,
                        num_pages=9, prefix_cache=on)  # 8 allocatable pages
        futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        for _ in range(300):
            if all(f.done() for f in futs):
                break
            eng.step()
            peak[on] = max(peak[on],
                           sum(r is not None for r in eng.slot_req))
        outs = [f.result(timeout=1) for f in futs]
        for p, got in zip(prompts, outs):
            assert got == _oracle(model, p, 12)
        if on:
            st = eng.stats()["prefix_cache"]
            assert st["shared_pages"] == 0  # drained: holds released
            assert st["hit_ratio"] > 0.65
    assert peak[True] == 4, "sharing should fit the whole batch at once"
    assert peak[False] <= 2, "unshared paged admission must not fit 4"


def test_prefix_eviction_then_reprefill_parity(model):
    """Pool pressure LRU-evicts unreferenced cached prefixes; a re-submit
    of the evicted prompt re-prefills from scratch and still matches the
    oracle bitwise (the eviction -> re-prefill cycle)."""
    rng = np.random.RandomState(55)
    pa = rng.randint(0, 1024, 40).astype(np.int32)
    pb = rng.randint(0, 1024, 40).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=4)  # 3 allocatable: A's cache must give way
    assert eng.generate(pa, max_new_tokens=4) == _oracle(model, pa, 4)
    assert eng.generate(pb, max_new_tokens=4) == _oracle(model, pb, 4)
    # B's admission had to evict A's pages (engine-local count)
    assert eng.stats()["prefix_cache"]["evictions"] > 0
    # the evicted prompt admits again, re-prefills, and stays exact
    assert eng.generate(pa, max_new_tokens=4) == _oracle(model, pa, 4)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


def test_prefix_cache_shared_pages_visible_midflight(model):
    """llm_kv_pages_shared_count / stats() see pages mapped by two slots
    plus the cache while both requests are in flight."""
    rng = np.random.RandomState(56)
    prompts = _mk_shared_prompts(rng, 64, (5, 9))
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32)
    f1 = eng.submit(prompts[0], max_new_tokens=20)
    f2 = eng.submit(prompts[1], max_new_tokens=20)
    for _ in range(6):
        eng.step()
    st = eng.stats()["prefix_cache"]
    assert sum(r is not None for r in eng.slot_req) == 2
    assert st["shared_pages"] >= 2  # the two full shared-prefix pages
    assert st["cached_pages"] >= 2
    eng.run_until_complete()
    assert f1.result(timeout=1) == _oracle(model, prompts[0], 20)
    assert f2.result(timeout=1) == _oracle(model, prompts[1], 20)


def test_prefix_impossible_total_need_is_shed(model):
    """Admission's impossibility check uses the TOTAL page need, not the
    unique (uncached) need: a cached prefix's pages occupy the same pool,
    so a prompt whose full table exceeds the pool can never complete even
    on a 100% hit — it must shed, not spin head-of-line forever (its own
    matched pages pin the cache against eviction)."""
    from paddle_tpu.inference import ServerOverloadedError

    rng = np.random.RandomState(53)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=4)  # 3 allocatable pages
    head = rng.randint(0, 1024, 40).astype(np.int32)
    f1 = eng.submit(head, max_new_tokens=4)  # caches ~2 pages of prefix
    eng.run_until_complete()
    assert f1.result(timeout=1) == _oracle(model, head, 4)
    assert eng.stats()["prefix_cache"]["cached_pages"] >= 1
    # extends the cached prefix: unique need fits the pool, total doesn't
    big = np.concatenate([head, rng.randint(0, 1024, 60).astype(np.int32)])
    f2 = eng.submit(big, max_new_tokens=30)  # needs 4 > 3 pages total
    eng.run_until_complete()
    with pytest.raises(ServerOverloadedError):
        f2.result(timeout=1)
    assert eng.stats()["llm_kv_pages_in_use"] == 0


def test_engine_with_gpt_family(gpt_model):
    """The engine is model-agnostic over the generate_step /
    prefill_chunk_step contract: the GPT family (learned positions, fused
    qkv block) serves with the same parity."""
    gpt = gpt_model
    rng = np.random.RandomState(9)
    p1 = rng.randint(0, gpt.config.vocab_size, 9).astype(np.int32)
    p2 = rng.randint(0, gpt.config.vocab_size, 21).astype(np.int32)
    eng = LLMEngine(gpt, max_batch_slots=2, max_seq_len=128, decode_chunk=2)
    f1 = eng.submit(p1, max_new_tokens=6)
    f2 = eng.submit(p2, max_new_tokens=6)
    eng.run_until_complete()
    for p, f in ((p1, f1), (p2, f2)):
        assert f.result(timeout=1) == _oracle(gpt, p, 6)


def test_drain_deadline_fails_remainder_loudly(model):
    """drain(deadline_s=) is the bounded SIGTERM drain: when it expires,
    everything still queued/in flight fails with DeadlineExceededError
    (counted, never silently dropped) and the engine ends EMPTY so
    shutdown can proceed."""
    from paddle_tpu.inference import llm_server as ls

    rng = np.random.RandomState(9)
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128)
    f_run = eng.submit(rng.randint(0, 1024, 8).astype(np.int32),
                       max_new_tokens=4)
    eng.step()  # admitted into the only slot, mid-decode
    f_queued = eng.submit(rng.randint(0, 1024, 8).astype(np.int32),
                          max_new_tokens=4)
    before = ls._M_DRAIN_EXPIRED.value
    assert eng.drain(deadline_s=0.0) is True  # expires immediately
    for f in (f_run, f_queued):
        with pytest.raises(ls.DeadlineExceededError):
            f.result(timeout=1)
    assert ls._M_DRAIN_EXPIRED.value == before + 2
    assert eng.slot_req == [None] and eng._pending.empty()  # truly empty
    # a deadline that is NOT hit behaves like the plain join
    eng.resume()
    f_ok = eng.submit(rng.randint(0, 1024, 8).astype(np.int32),
                      max_new_tokens=2)
    assert eng.drain(deadline_s=60.0) is True
    assert len(f_ok.result(timeout=1)) == 2
    assert ls._M_DRAIN_EXPIRED.value == before + 2  # no new expiries


# ------------------------------------------- randomness without eager calls
# A tick stages its compiled calls with no eager device program: the
# sampler's keys are derived INSIDE llm_decode / llm_spec_verify from the
# default generator's resident key and a host offset (Generator.fork()).
_RNG_ENGINES = {
    "paged": dict(kv_layout="paged", page_size=32, prefill_chunk=16),
    "paged-int8": dict(page_size=32, prefill_chunk=16, cache_dtype="int8"),
    "spec-paged": dict(kv_layout="paged", page_size=32, prefill_chunk=16,
                       spec_k=2),
}
_rng_engines = pytest.mark.parametrize("kind", sorted(_RNG_ENGINES))


def _rng_engine(model, kind):
    return LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                     **_RNG_ENGINES[kind])


def _compile_families():
    """{fn: count} of jit_compiles_total: the engine's program families
    (record_compile) and "backend", every XLA compile of the process."""
    from paddle_tpu import observability as obs

    fam = obs.snapshot().get("jit_compiles_total")
    return {x["labels"]["fn"]: x["value"] for x in fam["series"]} \
        if fam else {}


def _compiles():
    return sum(_compile_families().values())


@_rng_engines
def test_tick_issues_no_eager_device_call(model, kind, monkeypatch):
    """With every eager source of keys, and the dispatch of any eager
    primitive (a jnp.zeros, a jnp.asarray(int, dtype)), patched to raise
    while step() runs, a warmed engine serves a greedy and a sampled row
    side by side — COW forks of the prefix cache's tail pages included."""
    import jax
    from jax._src import dispatch

    from paddle_tpu.framework import random as fr

    rng = np.random.RandomState(60)
    p1 = rng.randint(0, 1024, 10).astype(np.int32)
    p2 = rng.randint(0, 1024, 7).astype(np.int32)
    eng = _rng_engine(model, kind)
    eng.warmup()
    f1 = eng.submit(p1, max_new_tokens=6)
    f2 = eng.submit(p2, max_new_tokens=6, do_sample=True, temperature=2.0,
                    top_k=50, top_p=0.95)

    def boom(*a, **k):
        raise AssertionError("eager device call inside a tick")

    offset0 = fr.default_generator()._offset
    with monkeypatch.context() as m:
        m.setattr(jax.random, "split", boom)
        m.setattr(fr.Generator, "split", boom)
        m.setattr(fr, "get_rng_key", boom)
        m.setattr(dispatch, "xla_primitive_callable", boom)
        for _ in range(40):
            if f1.done() and f2.done():
                break
            eng.step()
    assert f1.result(timeout=1) == _oracle(model, p1, 6,
                                           cache_dtype=eng.cache_dtype)
    got = f2.result(timeout=1)
    assert len(got) == 6 and all(0 <= t < 1024 for t in got)
    # every decode or verify call took one (key, offset) pair
    assert fr.default_generator()._offset > offset0
    assert eng.stats()["prefix_cache"]["cow_copies"] > 0


@_rng_engines
def test_seed_after_engine_build_governs_sampled_stream(model, kind):
    """The engine reads the default generator each tick: paddle.seed(s) on
    a LIVE engine restarts the stream (same seed, same tokens; another
    seed, other tokens).  The admission token is the engine's own host
    draw, pinned here so the runs differ only by the seed."""
    rng = np.random.RandomState(61)
    p = rng.randint(0, 1024, 10).astype(np.int32)
    eng = _rng_engine(model, kind)
    eng.warmup()

    def run(seed):
        paddle.seed(seed)
        eng._rng = np.random.default_rng(5)
        return eng.generate(p, max_new_tokens=12, do_sample=True,
                            temperature=5.0, top_p=0.99)

    a, again, b = run(101), run(101), run(202)
    assert len(a) == len(b) == 12
    assert a == again
    assert a[0] == b[0] and a != b


@_rng_engines
def test_warmed_engine_first_requests_compile_nothing(model, kind):
    """warmup() ran the programs with what a tick passes them, (key, offset)
    included: the first greedy and the first sampled request compile
    nothing, the engine's jits and the argument staging alike."""
    from paddle_tpu.observability import profiling as prof

    rng = np.random.RandomState(62)
    p = rng.randint(0, 1024, 13).astype(np.int32)
    eng = _rng_engine(model, kind)
    # the oracle's own compiles come first
    want = _oracle(model, p, 5, cache_dtype=eng.cache_dtype)
    try:
        eng.warmup()
        quiet = _compiles()
        assert eng.generate(p, max_new_tokens=5) == want
        assert _compiles() == quiet
        got = eng.generate(p, max_new_tokens=5, do_sample=True,
                           temperature=3.0, top_k=40, top_p=0.9)
        assert len(got) == 5
        assert _compiles() == quiet
    finally:
        prof.mark_warm(False)
