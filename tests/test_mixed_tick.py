"""A tick that carries a prefill chunk dispatches ONE program (llm_mixed).

For a model with ``mixed_step`` (Llama) the chunk's rows and the decode rows
go through one pass over the weights, and a final chunk's first token is read
where that program is booked: a tick later, after the next program is
dispatched.  Held here, on the CPU in float32: greedy tokens are the oracle's
(``generate()``) request for request in every way a chunk can meet decoding
rows; ``stats()["tick_pipeline"]["mixed"]`` counts the chunks that met one;
every chunk still leaves its span, its histogram observation and its count; a
warmed engine compiles nothing and calls nothing eagerly inside a tick; an
admission never makes the pump read before it dispatches; an engine whose
model lacks the step, or that speculates, scans several tokens a program or
holds an adapter pool, lists the programs it listed before."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.inference import DeadlineExceededError, LLMEngine  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402

from test_tick_pipeline import (  # noqa: E402
    _logged, _pipeline, _pool_balanced, _ticks)

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = LlamaForCausalLM(LlamaConfig.tiny(
        tensor_parallel=False, use_flash_attention=False,
        max_position_embeddings=256))
    m.eval()
    return m


def _oracle(model, prompt, n, **kw):
    ids = paddle.to_tensor(np.asarray(prompt, np.int32)[None, :])
    return [int(t) for t in
            np.asarray(model.generate(ids, max_new_tokens=n, **kw)._value)[0]]


def _engine(model, slots=3, **kw):
    kw.setdefault("page_size", 32)
    kw.setdefault("prefill_chunk", 16)
    return LLMEngine(model, max_batch_slots=slots, max_seq_len=128, **kw)


def _prompts(seed, *lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 1024, n).astype(np.int32) for n in lengths]


def _chunk_calls(eng):
    """Wraps the program that carries a chunk: the decode rows of each call
    (the page-table rows it did not mask to the trash page)."""
    real, rows = eng._get_chunk_prefill(), []

    def counting(*a, **k):
        rows.append(int((np.asarray(a[3])[:, 0] != 0).sum()))
        return real(*a, **k)

    eng._chunk_jit = counting
    return rows


def _serve(eng, first, later, steps=6):
    """``first`` [(prompt, new)] run for ``steps`` ticks, then ``later`` join."""
    futs = [eng.submit(p, max_new_tokens=k) for p, k in first]
    for _ in range(steps):
        eng.step()
    futs += [eng.submit(p, max_new_tokens=k) for p, k in later]
    eng.run_until_complete()
    return futs


# ------------------------------------------------- tokens, request for request
def _rows_decode_while_chunks_arrive(model):
    prompts = _prompts(41, 9, 14, 5, 23, 11)
    news = (15, 17, 6, 9, 8)
    eng = _engine(model)
    rows = _chunk_calls(eng)
    reqs = list(zip(prompts, news))
    futs = _serve(eng, reqs[:2], reqs[2:])
    assert max(rows) == 2  # a chunk rode beside two decoding rows
    return eng, rows, reqs, futs


def _a_prompt_of_five_chunks(model):
    (long_,) = _prompts(42, 70)
    eng = _engine(model)
    rows = _chunk_calls(eng)
    reqs = list(zip(_prompts(43, 8, 12), (14, 16))) + [(long_, 7)]
    futs = _serve(eng, reqs[:2], reqs[2:])
    assert rows[-5:] == [2] * 5  # every one of its chunks beside both rows
    return eng, rows, reqs, futs


def _a_prefix_hit_forks_its_tail_page(model):
    rng = np.random.RandomState(44)
    shared = rng.randint(0, 1024, 40).astype(np.int32)  # a page and a quarter
    prompts = [np.concatenate([shared, rng.randint(0, 1024, n).astype(np.int32)])
               for n in (3, 9, 5, 14)]
    eng = _engine(model, prefix_cache=True)
    rows = _chunk_calls(eng)
    reqs = list(zip(prompts, (12, 17, 6, 8)))
    futs = _serve(eng, reqs[:2], reqs[2:])
    pc = eng.stats()["prefix_cache"]
    assert pc["cow_copies"] > 0 and pc["hit_tokens"] >= 3 * 32
    return eng, rows, reqs, futs


def _a_final_chunk_fills_its_page(model):
    """Prompts of one and of two whole pages: the first decode token opens a
    page of its own; the repeat of the first prompt hits its page."""
    a, b = _prompts(45, 32, 64)
    eng = _engine(model, prefix_cache=True)
    rows = _chunk_calls(eng)
    reqs = [(a, 9), (b, 7), (a, 5), (_prompts(46, 16)[0], 6)]
    futs = _serve(eng, reqs[:2], reqs[2:], steps=8)
    return eng, rows, reqs, futs


def _int8_pages(model):
    eng = _engine(model, cache_dtype="int8")
    rows = _chunk_calls(eng)
    reqs = list(zip(_prompts(47, 9, 21, 6, 18), (12, 10, 7, 5)))
    futs = _serve(eng, reqs[:2], reqs[2:])
    return eng, rows, reqs, futs


@pytest.mark.parametrize("case", [
    _rows_decode_while_chunks_arrive, _a_prompt_of_five_chunks,
    _a_prefix_hit_forks_its_tail_page, _a_final_chunk_fills_its_page,
    _int8_pages], ids=lambda f: f.__name__.strip("_"))
def test_greedy_tokens_are_the_oracles_and_mixed_counts_the_chunks_that_met_a_row(
        model, case):
    chunks0 = obs.REGISTRY.get("llm_prefill_chunks_total").value
    eng, rows, reqs, futs = case(model)
    assert eng._mixed
    for (p, k), f in zip(reqs, futs):
        assert f.result(timeout=1) == _oracle(model, p, k,
                                              cache_dtype=eng.cache_dtype)
    pl = _pipeline(eng)
    assert pl["mixed"] == sum(n > 0 for n in rows) > 0
    # a chunk that met no row is a chunk all the same
    assert obs.REGISTRY.get("llm_prefill_chunks_total").value - chunks0 \
        == len(rows)
    assert pl["surplus_tokens"] == 0
    assert sum(pl["drained"].values()) == pl["drained"]["idle"]
    _pool_balanced(eng)


@pytest.mark.parametrize("how", ["eos", "max_new_tokens_1"])
def test_a_request_that_ends_with_its_first_token_never_decodes(model, how):
    """Its first token is read with the program that carried its final chunk;
    the slot is freed there and no program ever carries its row."""
    (p,) = _prompts(48, 21)
    others = list(zip(_prompts(49, 10, 13), (14, 12)))
    first = _oracle(model, p, 1)[0]
    eng = _engine(model, eos_token_id=first if how == "eos" else None)
    kw = dict(eos_token_id=first) if how == "eos" else {}
    want = [_oracle(model, q, k, **kw) for q, k in others]
    if how == "eos":  # the oracle pads past an EOS
        want = [w[:w.index(first) + 1] if first in w else w for w in want]
    futs = [eng.submit(q, max_new_tokens=k) for q, k in others]
    for _ in range(5):
        eng.step()
    f = eng.submit(p, max_new_tokens=9 if how == "eos" else 1)
    seen = []
    while not f.done():
        eng.step()
        seen.append(sum(map(eng._awaits_first, range(eng.n_slots))))
    assert f.result(timeout=1) == [first]
    assert 1 in seen  # it held its slot with the token in flight
    eng.run_until_complete()
    assert [g.result(timeout=1) for g in futs] == want
    assert _pipeline(eng)["surplus_tokens"] == 0
    _pool_balanced(eng)


def test_a_deadline_that_expires_with_the_final_chunk_in_flight(model):
    """The request holds its slot from the dispatch of its final chunk; its
    deadline passes before the program is read: it fails with no token, its
    pages go back, the logits are dropped where the program is booked, and
    the slot serves the next request."""
    t = [0.0]
    eng = _engine(model, slots=2, clock=lambda: t[0])
    doomed, other, nxt = _prompts(50, 12, 10, 9)
    g = eng.submit(other, max_new_tokens=12)
    for _ in range(3):
        eng.step()
    f = eng.submit(doomed, max_new_tokens=20, timeout=5.0)
    eng.step()  # its one chunk rides beside the other's row
    req, slot, _ = eng._inflight.first
    assert eng.slot_req[slot] is req and eng._awaits_first(slot)
    assert eng._prefilling is None
    t[0] = 10.0
    h = eng.submit(nxt, max_new_tokens=4)
    eng.step()  # expires it; the slot is the next request's at once
    with pytest.raises(DeadlineExceededError, match="after 0 generated tokens"):
        f.result(timeout=1)
    assert not req.tokens and eng.slot_req[slot] is not req
    eng.run_until_complete()
    assert g.result(timeout=1) == _oracle(model, other, 12)
    assert h.result(timeout=1) == _oracle(model, nxt, 4)
    assert _pipeline(eng)["mixed"] == 2
    _pool_balanced(eng)


def test_a_preemption_with_a_chunk_staged(model):
    """Two rows outgrow the pool on the tick that carries a third request's
    chunk: the pump reads the program in flight, preempts, and the staged
    chunk still rides this tick's program.  Every request finishes with the
    oracle's tokens."""
    pa, pb, pc_ = _prompts(51, 30, 30, 20)
    eng = _engine(model, num_pages=4)
    staged = []
    real = eng._preempt_slot

    def preempt(slot, origin="decode"):
        staged.append(eng._prefilling is not None)
        return real(slot, origin=origin)

    eng._preempt_slot = preempt
    fa = eng.submit(pa, max_new_tokens=8)
    fb = eng.submit(pb, max_new_tokens=8)
    for _ in range(5):
        eng.step()
    fc = eng.submit(pc_, max_new_tokens=5)  # two chunks of 16
    eng.run_until_complete()
    assert fa.result(timeout=1) == _oracle(model, pa, 8)
    assert fb.result(timeout=1) == _oracle(model, pb, 8)
    assert fc.result(timeout=1) == _oracle(model, pc_, 5)
    assert any(staged), staged
    pl = _pipeline(eng)
    assert pl["drained"]["preempt"] >= 1 and pl["surplus_tokens"] == 0
    _pool_balanced(eng)


# --------------------------------------------------------- the order of a tick
def test_a_final_chunk_tick_dispatches_before_it_reads(model):
    """A final chunk arrives while two rows decode: its tick stages, makes
    its ONE dispatch (the chunk aboard) and then reads the program before;
    the first token is read a tick later, behind that tick's dispatch.  No
    tick of the admission reads before it dispatches."""
    eng = _logged(_engine(model))
    a, b, c = _prompts(52, 9, 11, 14)
    fa = eng.submit(a, max_new_tokens=12)
    fb = eng.submit(b, max_new_tokens=12)
    for _ in range(5):
        eng.step()
    eng._phases.order.clear()
    fc = eng.submit(c, max_new_tokens=4)
    eng.step()
    eng.step()
    carry, read = _ticks(eng._phases.order)
    assert carry == ["expire", "admit", "prefill_stage", "bookkeep",
                     "decode_stage", "prefill_dispatch", "bookkeep",
                     "decode_sync", "bookkeep"]
    assert read == ["expire", "admit", "bookkeep", "decode_stage",
                    "decode_dispatch", "decode_sync", "bookkeep",
                    "first_token_sync", "bookkeep"]
    assert len(eng.slot_req[2].tokens) == 1 and not eng._awaits_first(2)
    eng.run_until_complete()
    for p, k, f in ((a, 12, fa), (b, 12, fb), (c, 4, fc)):
        assert f.result(timeout=1) == _oracle(model, p, k)
    pl = _pipeline(eng)
    assert pl["mixed"] == 1 and sum(pl["drained"].values()) == 1  # the end


# -------------------------------------------- compiles, eager calls, telemetry
def test_a_warmed_engine_compiles_nothing_and_calls_nothing_eagerly(
        model, monkeypatch):
    """The benchmark's window_compiles, on the CPU: after warmup() chunks
    meeting rows, a COW fork, a sampled row and a drain compile nothing, and
    no eager primitive runs between a tick's compiled calls."""
    import jax
    from jax._src import dispatch

    from paddle_tpu.observability import profiling as prof

    def compiles():
        fam = obs.snapshot().get("jit_compiles_total")
        return sum(x["value"] for x in fam["series"]) if fam else 0

    rng = np.random.RandomState(53)
    shared = rng.randint(0, 1024, 40).astype(np.int32)
    prompts = [np.concatenate([shared, rng.randint(0, 1024, n).astype(np.int32)])
               for n in (4, 11, 7, 30)]
    news = (10, 12, 6, 5)
    eng = _engine(model)
    try:
        eng.warmup()
        quiet = compiles()

        def boom(*a, **k):
            raise AssertionError("eager device call inside a tick")

        futs = [eng.submit(p, max_new_tokens=k)
                for p, k in zip(prompts[:2], news[:2])]
        with monkeypatch.context() as m:
            m.setattr(jax.random, "split", boom)
            m.setattr(dispatch, "xla_primitive_callable", boom)
            for _ in range(5):
                eng.step()
            futs += [eng.submit(p, max_new_tokens=k)
                     for p, k in zip(prompts[2:], news[2:])]
            futs.append(eng.submit(prompts[0][:9], max_new_tokens=6,
                                   do_sample=True, temperature=2.0, top_k=40))
            while eng._busy():
                eng.step()
        assert compiles() == quiet
        for p, k, f in zip(prompts, news, futs):
            assert f.result(timeout=1) == _oracle(model, p, k)
        assert len(futs[-1].result(timeout=1)) == 6
        pl = _pipeline(eng)
        assert pl["mixed"] >= 3
        assert eng.stats()["prefix_cache"]["cow_copies"] > 0
    finally:
        prof.mark_warm(False)


def test_every_chunk_leaves_its_span_its_observation_and_its_count(model):
    tracer = tracing.Tracer(store=tracing.TraceStore(capacity=16,
                                                     sample_every=1))
    hist = obs.REGISTRY.get("llm_prefill_chunk_seconds")._solo()
    n0, c0 = hist.count, obs.REGISTRY.get("llm_prefill_chunks_total").value
    eng = _engine(model, tracer=tracer)
    a, b, long_ = _prompts(54, 9, 12, 40)
    ids = ("m-a", "m-b", "m-long")
    futs = [eng.submit(p, max_new_tokens=k, trace_id=i)
            for p, k, i in zip((a, b), (12, 14), ids)]
    for _ in range(5):
        eng.step()
    futs.append(eng.submit(long_, max_new_tokens=5, trace_id=ids[2]))
    eng.run_until_complete()
    assert [len(f.result(timeout=1)) for f in futs] == [12, 14, 5]
    spans = {i: tracer.store.get_trace(i).find_spans("llm_prefill_chunk")
             for i in ids}
    assert [(s.attrs["index"], s.attrs["tokens"]) for s in spans["m-long"]] \
        == [(0, 16), (1, 16), (2, 8)]
    assert [len(spans[i]) for i in ids] == [1, 1, 3]
    assert all(s.duration_s is not None and s.duration_s > 0
               for v in spans.values() for s in v)
    assert hist.count - n0 == 5
    assert obs.REGISTRY.get("llm_prefill_chunks_total").value - c0 == 5
    assert _pipeline(eng)["mixed"] == 3  # the long prompt's chunks met rows
    # the first token closes the admission span a tick after its chunk
    adm = tracer.store.get_trace("m-long").find_spans("admission")[0]
    last = spans["m-long"][-1]
    assert adm.start_s + adm.duration_s > last.start_s + last.duration_s
    cnt = eng.stats()["tick_phases"]["count"]
    assert cnt["first_token_sync"] == 3 and cnt["prefill_dispatch"] == 5


# ------------------------------------------------- who keeps the two programs
class _NoStep(LlamaForCausalLM):
    """A Llama whose serving surface lacks the step."""
    mixed_step = None


def _names(eng):
    return [getattr(jit, "__name__", None) or jit.__wrapped__.__name__
            for jit, _, _ in eng._programs()]


def _hidden(model):
    m = _NoStep(model.config)
    m.set_state_dict(model.state_dict())
    m.eval()
    return _engine(m)


def _nemotron(model):
    from test_nemotron_h import engine
    return engine()


def _sala(model):
    from test_minicpm_sala import engine
    return engine(prefix_cache=True, state_checkpoints=2)


def _latent(model):
    from test_deepseek_v3 import engine
    return engine()


def _adapters(model):
    from paddle_tpu.models.lora import AdapterRegistry
    return _engine(model, adapters=AdapterRegistry(model, max_adapters=2,
                                                   rank=4))


_TWO = ["llm_prefill_chunk", "cow_copy_pages", "llm_decode"]


@pytest.mark.parametrize("make, want", [
    (_engine, ["llm_mixed", "cow_copy_pages", "llm_decode"]),
    (_hidden, _TWO),
    (lambda m: _engine(m, spec_k=2), _TWO + ["llm_spec_verify"]),
    (lambda m: _engine(m, decode_chunk=4), _TWO),
    (_adapters, _TWO),
    (_nemotron, ["llm_prefill_chunk", "llm_decode"]),
    (_sala, ["llm_prefill_chunk", "store", "load", "llm_decode"]),
    (_latent, _TWO),
], ids=["llama", "llama-step-hidden", "llama-spec", "llama-decode-chunk",
        "llama-adapters", "nemotron-h", "minicpm-sala", "deepseek-v3"])
def test_only_an_engine_that_can_take_one_pass_lists_the_mixed_program(
        model, make, want):
    eng = make(model)
    assert _names(eng) == want
    assert eng._mixed == (want[0] == "llm_mixed")


def test_an_engine_whose_model_hides_the_step_serves_as_before(model):
    """The parent's schedule: a final chunk's logits are read at the end of
    the tick that dispatched it; nothing is staged, nothing counted mixed."""
    eng = _logged(_hidden(model))
    reqs = list(zip(_prompts(55, 9, 14, 23), (9, 11, 6)))
    futs = _serve(eng, reqs[:2], reqs[2:], steps=4)
    for (p, k), f in zip(reqs, futs):
        assert f.result(timeout=1) == _oracle(model, p, k)
    assert _pipeline(eng)["mixed"] == 0 and eng._staged is None
    for t in _ticks(eng._phases.order):
        if "first_token_sync" in t:
            assert "prefill_dispatch" in t[:t.index("first_token_sync")]
    _pool_balanced(eng)
