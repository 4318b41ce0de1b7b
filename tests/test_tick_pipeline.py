"""The pump keeps one decode program in flight (LLMEngine._decode_tick).

A tick dispatches its decode program before it reads the previous tick's
result; the tokens feed the next program on the device and are booked one
program late.  Held here: greedy tokens are the oracle's with requests joining
mid-flight, on the three architectures; the order of a tick; every case in
which the pump reads first (a constrained row, a speculating engine, a
preemption, nothing left to run, stop / drain / pump death, warmup()); an EOS
learnt one program late costs exactly one counted surplus token, a finish by
length none; decode_chunk > 1; the phases, the goodput carves and the layers'
counts keep adding up."""
import os
import sys
import threading

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from benchmark import serve_hybrid, serve_sala  # noqa: E402
from benchmark.reference import minicpm_sala_ref, nemotron_h_ref  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.inference import LLMEngine, llm_server  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.observability.spans import PhaseClock  # noqa: E402

from test_minicpm_sala import tiny_cfg as sala_cfg  # noqa: E402
from test_nemotron_h import tiny_cfg as nemotron_cfg  # noqa: E402

pytestmark = pytest.mark.quick
SEED = 7


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = LlamaForCausalLM(LlamaConfig.tiny(
        tensor_parallel=False, use_flash_attention=False,
        max_position_embeddings=256))
    m.eval()
    return m


def _oracle(model, prompt, n):
    ids = paddle.to_tensor(np.asarray(prompt, np.int32)[None, :])
    return list(np.asarray(model.generate(ids, max_new_tokens=n)._value)[0])


def _engine(model, slots=2, **kw):
    kw.setdefault("page_size", 32)
    kw.setdefault("prefill_chunk", 16)
    return LLMEngine(model, max_batch_slots=slots, max_seq_len=128, **kw)


def _prompts(seed, *lengths, vocab=1024):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lengths]


def _pipeline(eng):
    return eng.stats()["tick_pipeline"]


def _run_synchronously(eng):
    """The reference: a synchronous pump, every decode result read in the
    tick that dispatched its program."""
    while eng._busy():
        eng.step()
        with eng._lock:
            if eng._inflight is not None:
                eng._read_decode("idle")


def _pool_balanced(eng):
    """Every page is free or held by the prefix cache alone, no slot is
    taken, nothing is in flight."""
    assert eng._inflight is None and eng._chunk_out is None
    assert not eng._ahead.any() and eng.slot_req == [None] * eng.n_slots
    assert eng.stats()["llm_kv_pages_in_use"] == 0
    assert len(eng._free_pages) + int(eng._page_cached.sum()) \
        == eng.num_pages - 1


class _LoggedClock(PhaseClock):
    """Keeps the order of a tick's phase switches; ticks end with None."""

    __slots__ = ("order",)

    def __init__(self, prefix, phases):
        super().__init__(prefix, phases)
        self.order = []

    def switch(self, phase):
        self.order.append(phase)
        return super().switch(phase)

    def end(self):
        self.order.append(None)
        return super().end()


def _logged(eng):
    eng._phases = _LoggedClock("llm_tick", llm_server._TICK_PHASES)
    return eng


def _ticks(order):
    out, cur = [], []
    for p in order:
        if p is None:
            out.append(cur)
            cur = []
        else:
            cur.append(p)
    return out


# ------------------------------------------------ tokens, three architectures
def _llama_case(model, prefix_cache):
    """Two requests decode, three more join mid-flight; with the prefix
    cache on the prompts share 40 tokens (a page and a quarter), so the
    joiners fork the shared tail page copy-on-write."""
    rng = np.random.RandomState(3)
    shared = rng.randint(0, 1024, 40).astype(np.int32)
    prompts = [np.concatenate([shared, rng.randint(0, 1024, n).astype(np.int32)])
               for n in (3, 9, 5, 14, 7)]
    news = (9, 17, 6, 12, 8)
    eng = _engine(model, slots=3, prefix_cache=prefix_cache)
    futs = [eng.submit(p, max_new_tokens=k)
            for p, k in zip(prompts[:2], news[:2])]
    for _ in range(6):
        eng.step()
    assert eng._inflight is not None  # hand-driven: a result is in flight
    futs += [eng.submit(p, max_new_tokens=k)
             for p, k in zip(prompts[2:], news[2:])]
    eng.run_until_complete()
    for p, k, f in zip(prompts, news, futs):
        assert f.result(timeout=1) == _oracle(model, p, k)
    if prefix_cache:
        assert eng.stats()["prefix_cache"]["cow_copies"] > 0
    return eng


def _hybrid_case(build, cfg, ref, engine_kw, prompts, news, after=None):
    """The same for a model with recurrent state: every served token's logit
    lies within 1e-4 of the float32 reference's best, and the tokens are the
    synchronous pump's."""
    def run(pump):
        eng = LLMEngine(build(cfg, SEED), **engine_kw)
        with jax.default_matmul_precision("highest"):
            futs = [eng.submit(p, max_new_tokens=k)
                    for p, k in zip(prompts[:2], news[:2])]
            for _ in range(5):
                eng.step()
            futs += [eng.submit(p, max_new_tokens=k)
                     for p, k in zip(prompts[2:], news[2:])]
            pump(eng)
        return eng, [f.result(timeout=1) for f in futs]

    eng, got = run(LLMEngine.run_until_complete)
    _, want = run(_run_synchronously)
    assert got == want
    with jax.default_matmul_precision("highest"):
        gaps, _ = ref.served_gap(
            cfg, SEED, [(p, np.asarray(t, np.int32))
                        for p, t in zip(prompts, got)], 128)
    assert len(gaps) == sum(news) and float(np.max(gaps)) < 1e-4
    if after:
        after(eng)
    return eng


def _nemotron_case(model):
    rng = np.random.default_rng([SEED, 1])
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (20, 9, 33, 12)]
    return _hybrid_case(
        serve_hybrid.build_model, nemotron_cfg(), nemotron_h_ref,
        dict(max_batch_slots=3, max_seq_len=128, page_size=16, num_pages=25,
             prefill_chunk=16), prompts, (10, 7, 5, 9))


def _sala_case(model):
    """Questions to one 32-token document (four whole pages): every one
    after the first resumes the document's state checkpoint."""
    rng = np.random.default_rng([SEED, 2])
    doc = rng.integers(0, 256, 32, dtype=np.int32)
    prompts = [doc] + [np.concatenate([doc, rng.integers(0, 256, n, dtype=np.int32)])
                       for n in (5, 11, 3)]

    def after(eng):
        ck = eng.stats()["recurrent_state"]["checkpoints"]
        assert ck["stored"] >= 1 and ck["restored"] >= 2

    return _hybrid_case(
        serve_sala.build_model, sala_cfg(), minicpm_sala_ref,
        dict(max_batch_slots=3, max_seq_len=128, page_size=8, num_pages=49,
             prefill_chunk=8, prefix_cache=True, state_checkpoints=2),
        prompts, (2, 9, 6, 8), after)


@pytest.mark.parametrize("case", [
    pytest.param(lambda m: _llama_case(m, True), id="llama-prefix-cache-cow"),
    pytest.param(lambda m: _llama_case(m, False), id="llama-no-prefix-cache"),
    pytest.param(_nemotron_case, id="nemotron-h"),
    pytest.param(_sala_case, id="minicpm-sala-checkpoint-resume"),
])
def test_greedy_tokens_are_the_oracles_with_requests_joining_mid_flight(
        model, case):
    eng = case(model)
    pl = _pipeline(eng)
    assert pl["overlapped"] > 0 and pl["surplus_tokens"] == 0
    assert pl["drained"]["idle"] >= 1 and sum(pl["drained"].values()) \
        == pl["drained"]["idle"]  # nothing else made it read first
    _pool_balanced(eng)


# -------------------------------------------------------- the order of a tick
def test_a_tick_dispatches_before_it_reads(model):
    """Steady state: decode_stage, the tick's ONE dispatch (tick k:
    decode_dispatch, or prefill_dispatch where a chunk rides the program),
    then decode_sync and bookkeep (tick k-1's result).  The first program
    has nothing to read, the last tick only reads; a first token is read
    with the program that carried its final chunk — a tick after that was
    dispatched, behind this tick's dispatch and that program's own tokens.
    Every program dispatched is read exactly once."""
    eng = _logged(_engine(model, slots=2))
    long_, short = _prompts(11, 9, 20)
    f1 = eng.submit(long_, max_new_tokens=7)
    eng.step()  # admission alone: no row to decode yet
    f2 = eng.submit(short, max_new_tokens=3)  # two chunks of 16
    eng.run_until_complete()
    assert f1.result(timeout=1) == _oracle(model, long_, 7)
    assert f2.result(timeout=1) == _oracle(model, short, 3)
    ticks = _ticks(eng._phases.order)
    dec = [[p for p in t if p.startswith("decode_") or p == "prefill_dispatch"]
           for t in ticks]
    dec = [d for d in dec if d]
    assert dec[0] == ["decode_stage", "prefill_dispatch"]  # nothing in flight
    assert dec[-1] == ["decode_sync"]                     # nothing left to run
    assert all(d[0] == "decode_stage" and d[2:] == ["decode_sync"]
               for d in dec[1:-1]) and len(dec) == 9
    # three programs carried a chunk (the second prompt's first chunk rode
    # alone: the first request had no token yet), five decoded only
    assert [d[1] for d in dec[:-1]] == ["prefill_dispatch"] * 3 \
        + ["decode_dispatch"] * 5
    firsts = [t for t in ticks if "first_token_sync" in t]
    assert len(firsts) == 2
    for t in firsts:
        # this tick's program is out, the program that carried the final
        # chunk is read: its rows' tokens are booked, then its first token
        i = t.index("first_token_sync")
        assert t[i - 2:] == ["decode_sync", "bookkeep",
                             "first_token_sync", "bookkeep"]
        assert t.index("decode_stage") < i - 2 and t[i - 3] in (
            "decode_dispatch", "bookkeep")  # bookkeep: closes prefill_dispatch
    cnt = eng.stats()["tick_phases"]["count"]
    assert cnt["decode_stage"] == cnt["decode_sync"] == 8 \
        == cnt["decode_dispatch"] + cnt["prefill_dispatch"]
    pl = _pipeline(eng)
    assert pl["overlapped"] == 7 and pl["drained"]["idle"] == 1
    assert pl["mixed"] == 1  # the final chunk that met a decoding row
    fam = obs.REGISTRY.get("llm_tick_pipeline_ticks_total")
    assert {lv[0] for lv, _ in fam.series()} \
        == {"overlapped", "mixed", *llm_server._DRAIN_REASONS}


def test_only_a_drained_engine_is_idle(model):
    """A hand-driven step() leaves a result in flight; run_until_complete(),
    generate(), drain() and the background pump's idle test do not."""
    eng = _engine(model, slots=1)
    (p,) = _prompts(12, 10)
    f = eng.submit(p, max_new_tokens=5)
    eng.step()  # the chunk's program goes out
    assert eng._inflight is not None and eng._awaits_first(0)
    eng.step()  # ... is read: the first token
    assert eng._inflight is None and eng._busy() and not eng._drained()
    eng.step()  # the first decode program
    assert eng._inflight is not None and eng._busy() and not eng._drained()
    assert int(eng._ahead[0]) == 1 and len(eng.slot_req[0].tokens) == 1
    eng.run_until_complete()
    assert f.result(timeout=1) == _oracle(model, p, 5)
    _pool_balanced(eng)
    assert not eng._busy() and eng._drained()
    # a row that meets its EOS leaves the program after it in flight with no
    # row alive: still busy, until that is read
    base = _oracle(model, p, 6)
    eos_eng = _engine(model, slots=1, eos_token_id=base[2])
    g = eos_eng.submit(p, max_new_tokens=6)
    while not g.done():
        eos_eng.step()
    assert eos_eng.slot_req == [None] and eos_eng._inflight is not None
    assert eos_eng._busy() and not eos_eng._drained()
    assert eos_eng.drain(timeout=30.0) is True
    _pool_balanced(eos_eng)


# ----------------------------------------- when the pump does NOT run ahead
def test_a_constrained_row_is_read_before_its_next_mask(model):
    """A constrained request joins two unconstrained ones that are running
    ahead: from its first decode tick every result is read in the tick that
    dispatched it (the mask follows the token), and when it has finished the
    pump runs ahead again.  Tokens: the solo oracle's under the same mask."""
    from paddle_tpu.inference.constrain import compile_constraint

    V = 1024
    eos = V - 1
    vocab = [str(i) if i < 10 else f"w{i}" for i in range(V)]
    vocab[eos] = "</s>"
    eng = _logged(_engine(model, slots=3, eos_token_id=eos,
                          constraint_vocab=vocab))
    free = _prompts(13, 12, 7)
    (held,) = _prompts(14, 8)
    futs = [eng.submit(p, max_new_tokens=k) for p, k in zip(free, (14, 16))]
    for _ in range(4):
        eng.step()
    assert _pipeline(eng)["overlapped"] >= 1
    fc = eng.submit(held, max_new_tokens=4, constraint=r"[0-9][0-9][0-9]")
    seen = []
    while not fc.done():
        eng.step()
        if any(r is not None and r.cursor is not None for r in eng.slot_req):
            seen.append(eng._inflight is None and not eng._ahead.any())
    assert len(seen) >= 2 and all(seen[1:])  # synchronous while it decodes
    drained = _pipeline(eng)["drained"]["constrained"]
    assert drained >= 3  # the transition's read + one a constrained tick
    before = _pipeline(eng)["overlapped"]
    eng.run_until_complete()
    assert _pipeline(eng)["overlapped"] > before  # running ahead again
    assert _pipeline(eng)["drained"]["constrained"] == drained

    def solo(p, n, **kw):
        ids = paddle.to_tensor(np.asarray(p, np.int32)[None, :])
        out = np.asarray(model.generate(ids, max_new_tokens=n,
                                        eos_token_id=eos, **kw)._value)[0]
        toks = []
        for t in out:
            toks.append(int(t))
            if int(t) == eos:
                break
        return toks

    tc = compile_constraint(r"[0-9][0-9][0-9]", vocab, eos)
    got = fc.result(timeout=1)
    assert got == solo(held, 4, token_mask_fn=tc)
    assert all(t < 10 for t in got[:3]) and got[3] == eos
    for p, k, f in zip(free, (14, 16), futs):
        assert f.result(timeout=1) == solo(p, k)
    assert _pipeline(eng)["surplus_tokens"] == 0
    _pool_balanced(eng)


def test_a_speculating_engine_reads_every_result_at_once(model):
    """spec_k: the drafter reads the tokens, so nothing stays in flight —
    the plain decode ticks near the cache's end included."""
    eng = _engine(model, slots=2, spec_k=2)
    (p,) = _prompts(15, 100)  # the last strides before capacity fall back
    f = eng.submit(p, max_new_tokens=40)  # the cache ends first: row 127
    while not f.done():
        eng.step()
        assert eng._inflight is None and not eng._ahead.any()
    got = f.result(timeout=1)
    assert len(got) == 28 and got == _oracle(model, p, 28)
    pl = _pipeline(eng)
    assert pl["overlapped"] == 0 and pl["drained"]["spec"] >= 1
    assert pl["drained"]["spec"] == sum(pl["drained"].values())
    _pool_balanced(eng)


def test_preemption_reads_the_result_in_flight_and_regrows_from_all_tokens(
        model):
    """Two requests outgrow a pool of two pages: the pump reads the result in
    flight before it preempts (the regrown prompt holds every token), and
    both finish with the oracle's tokens."""
    pa, pb = _prompts(25, 30, 30)
    eng = _engine(model, slots=2, prefill_chunk=32, num_pages=3)
    fa = eng.submit(pa, max_new_tokens=6)
    fb = eng.submit(pb, max_new_tokens=6)
    eng.run_until_complete()
    assert fa.result(timeout=1) == _oracle(model, pa, 6)
    assert fb.result(timeout=1) == _oracle(model, pb, 6)
    pl = _pipeline(eng)
    assert pl["drained"]["preempt"] >= 1 and pl["surplus_tokens"] == 0
    assert obs.REGISTRY.get("llm_page_preemptions_total").value >= 1
    _pool_balanced(eng)


def test_deadline_expiry_with_a_token_in_flight_drops_it(model):
    from paddle_tpu.inference import DeadlineExceededError

    t = [0.0]
    eng = _engine(model, slots=2, clock=lambda: t[0])
    doomed, other = _prompts(27, 10, 12)
    f = eng.submit(doomed, max_new_tokens=50, timeout=5.0)
    g = eng.submit(other, max_new_tokens=9)
    for _ in range(5):
        eng.step()
    req = eng.slot_req[0]
    booked = len(req.tokens)
    assert int(eng._ahead[0]) == 1  # a token of its is in flight
    t[0] = 10.0
    eng.step()  # expires it, reads the program that carried it
    with pytest.raises(DeadlineExceededError,
                       match=f"after {booked} generated tokens"):
        f.result(timeout=1)
    assert len(req.tokens) == booked and _pipeline(eng)["surplus_tokens"] == 1
    assert eng.slot_req[0] is None and int(eng._ahead[0]) == 0
    eng.run_until_complete()
    assert g.result(timeout=1) == _oracle(model, other, 9)
    assert _pipeline(eng)["surplus_tokens"] == 1
    _pool_balanced(eng)


def _die_in_decode(eng):
    real = eng._get_decode(1)
    calls = [0]

    def dying(*a, **k):
        calls[0] += 1
        if calls[0] == 3:
            raise RuntimeError("injected decode fault")
        return real(*a, **k)

    eng._decode_jit[1] = dying


@pytest.mark.parametrize("how", ["stop", "drain", "drain_deadline",
                                 "pump_death"])
def test_stop_drain_and_pump_death_leave_no_future_pending(model, how):
    eng = _engine(model, slots=2)
    prompts = _prompts(28, 10, 14, 9)
    if how == "pump_death":
        _die_in_decode(eng)
    futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    if how in ("stop", "pump_death"):
        eng.start()
        try:
            if how == "stop":
                while not futs[0].done() and eng._inflight is None:
                    threading.Event().wait(0.001)
            else:
                for f in futs:
                    with pytest.raises(RuntimeError, match="pump thread died"):
                        f.result(timeout=60)
        finally:
            eng.stop()
        for f in futs:
            assert f.done()
            if not f.exception(timeout=1):
                assert len(f.result()) == 12
    else:
        for _ in range(4):
            eng.step()
        assert eng._inflight is not None
        if how == "drain":
            assert eng.drain(timeout=60.0) is True
            for p, f in zip(prompts, futs):
                assert f.result(timeout=1) == _oracle(model, p, 12)
        else:
            assert eng.drain(deadline_s=0.0) is True
            for f in futs:
                with pytest.raises(llm_server.DeadlineExceededError):
                    f.result(timeout=1)
            assert _pipeline(eng)["drained"]["stop"] == 1
    _pool_balanced(eng)
    if how == "pump_death":
        return  # a dead pump's engine refuses new work until start()
    # clean and reusable: the next request takes its first token from the host
    eng.resume()
    (p,) = _prompts(29, 11)
    assert eng.generate(p, max_new_tokens=5) == _oracle(model, p, 5)


# ------------------------------------------------------------- surplus tokens
def _carried(eng):
    """Wraps the engine's programs: the real rows of each call that decodes
    (the page-table rows it did not mask to the trash page) — every
    llm_decode, and an llm_mixed where rows rode beside the chunk."""
    eff = max(1, eng.decode_chunk)
    rows = []

    def counting(real, always):
        def call(*a, **k):
            n = int((np.asarray(a[3])[:, 0] != 0).sum())
            if n or always:
                rows.append(n)
            return real(*a, **k)
        return call

    eng._decode_jit[eff] = counting(eng._get_decode(eff), True)
    if eng._mixed:
        eng._chunk_jit = counting(eng._get_chunk_prefill(), False)
    return rows


def test_an_eos_one_program_late_drops_exactly_one_surplus_token(model):
    (p,) = _prompts(3, 10)
    base = _oracle(model, p, 8)
    eng = _engine(model, slots=1, eos_token_id=base[3])
    rows = _carried(eng)
    assert eng.generate(p, max_new_tokens=8) == base[:4]
    # three decode tokens booked, a fourth computed before the EOS was read:
    # dropped, counted, its page provided for
    assert rows == [1, 1, 1, 1]
    pl = _pipeline(eng)
    assert pl["surplus_tokens"] == 1 and pl["overlapped"] == 3
    # the chunk's program, read with nothing else to run, and the last one
    assert pl["drained"]["idle"] == 2
    _pool_balanced(eng)


def test_a_finish_by_length_computes_no_surplus_row(model):
    """Every row a program carried produced a token that was booked: a row
    that reaches max_new_tokens (or the end of its cache) with the token in
    flight is left out of the next program."""
    prompts = _prompts(31, 9, 17, 5, 26)
    news = (5, 2, 9, 1)
    eng = _engine(model, slots=2)
    rows = _carried(eng)
    futs = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    eng.run_until_complete()
    for p, k, f in zip(prompts, news, futs):
        assert f.result(timeout=1) == _oracle(model, p, k)
    assert sum(rows) == sum(k - 1 for k in news) and 0 not in rows
    assert _pipeline(eng)["surplus_tokens"] == 0
    # and at the end of the cache: L - 1 = 127 rows, 120 of them the prompt
    (long_,) = _prompts(32, 120)
    eng = _engine(model, slots=1)
    rows = _carried(eng)
    got = eng.generate(long_, max_new_tokens=50)
    assert got == _oracle(model, long_, 50)[:len(got)] and len(got) == 8
    assert sum(rows) == 7 and _pipeline(eng)["surplus_tokens"] == 0


def test_decode_chunk_4_rides_the_same_path(model):
    """Four tokens a program: the feed is the chunk's last column, a row
    that ends inside a chunk drops the rest of it as before, and one that
    ends WITH the chunk in flight is left out of the next."""
    prompts = _prompts(33, 29, 11, 6)
    news = (10, 13, 4)
    eng = _engine(model, slots=2, decode_chunk=4)
    rows = _carried(eng)
    futs = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None and eng._inflight.eff == 4
    eng.run_until_complete()
    for p, k, f in zip(prompts, news, futs):
        assert f.result(timeout=1) == _oracle(model, p, k)
    pl = _pipeline(eng)
    assert pl["overlapped"] > 0 and pl["surplus_tokens"] == 0
    # 9, 12 and 3 decode tokens in chunks of four: 3 + 3 + 1 row-programs
    assert sum(rows) == 7
    _pool_balanced(eng)


# ------------------------------------------------ clocks, ledgers and counts
def test_phases_and_goodput_add_up_when_the_pump_drains(model):
    """The preempting engine again (reads out of order, stages twice): the
    phases still exhaust the tick histogram and the ledger's decode bucket
    is still the three decode_* phases."""
    h = obs.REGISTRY.get("llm_decode_tick_duration_seconds")._solo()
    s0 = h.sum
    pa, pb = _prompts(25, 30, 30)
    eng = _engine(model, slots=2, prefill_chunk=32, num_pages=3)
    futs = [eng.submit(p, max_new_tokens=6) for p in (pa, pb)]
    eng.run_until_complete()
    assert all(len(f.result(timeout=1)) == 6 for f in futs)
    assert _pipeline(eng)["drained"]["preempt"] >= 1
    ph = eng.stats()["tick_phases"]
    sec = ph["seconds"]
    assert sum(sec.values()) == pytest.approx(h.sum - s0, rel=0.03)
    assert sum(sec.values()) <= h.sum - s0
    buckets = eng._goodput.check()["buckets"]
    assert buckets["decode"] == pytest.approx(
        sec["decode_stage"] + sec["decode_dispatch"] + sec["decode_sync"],
        abs=2e-6)
    assert ph["host_s"] == pytest.approx(
        sum(sec.values()) - sec["decode_sync"] - sec["first_token_sync"],
        abs=1e-9)


def _counted_case(name):
    if name == "nemotron-h":
        eng_kw = dict(max_batch_slots=1, max_seq_len=128, page_size=16,
                      num_pages=25, prefill_chunk=16)
        return (lambda: LLMEngine(serve_hybrid.build_model(nemotron_cfg(), SEED),
                                  **eng_kw)), "moe", 2
    eng_kw = dict(max_batch_slots=1, max_seq_len=128, page_size=8,
                  num_pages=49, prefill_chunk=8)
    # a block-sparse layer counts a call a real row: one here
    return (lambda: LLMEngine(serve_sala.build_model(sala_cfg(), SEED),
                              **eng_kw)), "sparse_attention", 2


@pytest.mark.parametrize("name", ["nemotron-h", "minicpm-sala"])
def test_the_layers_counts_belong_to_the_program_that_made_them(name):
    """The experts' pairs and the sparse layers' blocks ride home with a
    program's tokens: after each hand-driven tick the published decode
    calls are those of the programs READ so far (not dispatched), and after
    a drained run every count equals the synchronous pump's."""
    make, block, layers = _counted_case(name)
    rng = np.random.default_rng([SEED, 5])
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (12, 30, 7)]
    news = (6, 4, 9)

    def counts(eng):
        st = eng.stats()[block]
        return {p: dict(st[p]) for p in ("decode", "prefill")}

    eng = make()
    futs = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    while eng._busy():
        eng.step()
        read = eng.stats()["tick_phases"]["count"]["decode_sync"]
        assert counts(eng)["decode"]["layer_calls"] == layers * read
    got = [f.result(timeout=1) for f in futs]
    assert eng.stats()["tick_phases"]["count"]["decode_dispatch"] \
        == sum(k - 1 for k in news)
    sync = make()
    futs = [sync.submit(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    _run_synchronously(sync)
    assert got == [f.result(timeout=1) for f in futs]
    assert counts(eng) == counts(sync)
    assert _pipeline(eng)["overlapped"] > 0
    assert _pipeline(sync)["overlapped"] == 0


def test_a_warmed_engine_compiles_nothing_with_rows_joining_and_leaving(model):
    """warmup() runs the decode program fed by the resident zeros, one
    signature with a program's own last tokens: the first tick, a tick that
    mixes rows fed from the host with rows fed from the device, and a drain
    compile nothing (the chip's reading: PERF.md §6, PR 32)."""
    from paddle_tpu.observability import profiling as prof

    def compiles():
        fam = obs.snapshot().get("jit_compiles_total")
        return sum(x["value"] for x in fam["series"]) if fam else 0

    prompts = _prompts(34, 13, 40, 6)
    news = (7, 5, 9)
    want = [_oracle(model, p, k) for p, k in zip(prompts, news)]
    eng = _engine(model, slots=2)
    try:
        eng.warmup()
        quiet = compiles()
        futs = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, news)]
        eng.run_until_complete()
        assert [f.result(timeout=1) for f in futs] == want
        assert compiles() == quiet
        # warmup() on an engine that still has a dead program in flight
        eos_eng = _engine(model, slots=1, eos_token_id=want[0][2])
        g = eos_eng.submit(prompts[0], max_new_tokens=7)
        while not g.done():
            eos_eng.step()
        assert eos_eng._inflight is not None
        eos_eng.warmup()
        assert eos_eng._inflight is None
        assert _pipeline(eos_eng)["drained"]["warmup"] == 1
        assert _pipeline(eos_eng)["surplus_tokens"] == 1
    finally:
        prof.mark_warm(False)
