"""Device seconds by (program, named scope) (ISSUE 35): the census of a
compiled program (module, instruction -> scope, bytes, operations), the
reducer that joins a profiler dump against it, and the engine's two calls
(``program_census()``, ``profile_device()``).

Oracles: a hand-encoded dump whose seconds are known by construction (two
modules that both hold ``fusion.3``, a ``while`` with children, an event
outside every module, a module the census lacks); tiny engines of the four
model families for the census's shape; a spy on ``jax.jit`` for "never from
``__init__``, ``warmup()`` or a tick"; a live CPU profile for the engine's
call.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed import census
from paddle_tpu.inference import LLMEngine, llm_server
from paddle_tpu.observability import xplane
from paddle_tpu.observability.metrics import REGISTRY

from test_xplane import _ld, _map_entry, _vint

DECODE, CHUNK = "jit_llm_decode", "jit_llm_prefill_chunk"


# ------------------------------------------------------- (a) synthetic dump
def _event(meta_id, start_ns, dur_ns):
    return _ld(4, _vint(1, meta_id) + _vint(2, start_ns * 1000)
               + _vint(3, dur_ns * 1000))


def _v5e_dump():
    """One TPU plane as a v5e writes it: an op event is named by its whole
    instruction and carries no module; the modules are a line of their own."""
    names = {
        1: f"{DECODE}(11)", 2: f"{CHUNK}(12)", 3: "jit_alien(7)",
        4: "%fusion.3 = bf16[4]{0} fusion(%p.1), kind=kLoop",
        5: "%while.1 = (s32[], bf16[4]{0}) while(%tuple.2), body=%b",
        6: "%fusion.9 = bf16[4]{0} fusion(%p.2), kind=kLoop",
        7: "%copy.2 = bf16[4]{0} copy(%fusion.9)",
        8: "%stray.1 = bf16[4]{0} copy(%p.3)",
    }
    meta = b"".join(_map_entry(4, k, v) for k, v in names.items())
    modules = _ld(3, _vint(1, 1) + _ld(2, b"XLA Modules") + _vint(3, 0)
                  + _event(1, 1000, 4000) + _event(2, 6000, 3000)
                  + _event(3, 10000, 1000))
    ops = _ld(3, _vint(1, 2) + _ld(2, b"XLA Ops") + _vint(3, 0)
              + _event(4, 1100, 500)        # decode: fusion.3 (mlp)
              + _event(5, 2000, 2000)       # decode: the while
              + _event(6, 2100, 400)        # ... its child, in the census
              + _event(7, 2600, 400)        # ... its child, no census row
              + _event(4, 6100, 1000)       # chunk: fusion.3 (attention)
              + _event(8, 9200, 100)        # outside every module
              + _event(4, 10100, 500))      # a module the census lacks
    return _ld(1, _vint(1, 1) + _ld(2, b"/device:TPU:0") + modules + ops
               + meta)


def _row(scope, opcode="fusion", nbytes=8, flops=0):
    return {"scope": scope, "opcode": opcode, "bytes": nbytes, "flops": flops}


_CENSUS = {
    DECODE: {"fusion.3": _row("mlp", flops=100),
             "while.1": _row("unscoped", "while", 0),
             "fusion.9": _row("attention/paged_attention")},
    CHUNK: {"fusion.3": _row("attention")},
}


def test_seconds_land_by_module_and_scope_and_self_time_adds_up():
    red = xplane.device_seconds(xplane.parse_xspace(_v5e_dump()), _CENSUS)
    ns = 1e-9
    dec, chk = red["programs"][DECODE], red["programs"][CHUNK]
    # the same instruction name in two programs: two scopes, two rows
    assert dec["scopes"]["mlp"] == {"seconds": pytest.approx(500 * ns),
                                    "events": 1, "bytes": 8, "flops": 100}
    assert chk["scopes"] == {"attention": {
        "seconds": pytest.approx(1000 * ns), "events": 1, "bytes": 8,
        "flops": 0}}
    # the while counts its span less what its children cover
    assert dec["scopes"]["unscoped"]["seconds"] == pytest.approx(1200 * ns)
    assert dec["unscoped_s"] == pytest.approx(1200 * ns)
    assert dec["scopes"]["attention/paged_attention"]["seconds"] \
        == pytest.approx(400 * ns)
    assert dec["ops"]["while.1"]["span_s"] == pytest.approx(2000 * ns)
    # a child with no census row: unmatched, by family, under its program
    assert dec["unmatched"] == {"copy": pytest.approx(400 * ns)}
    assert dec["unmatched_s"] == pytest.approx(400 * ns)
    assert (dec["calls"], chk["calls"]) == (1, 1)
    assert dec["seconds"] == pytest.approx(2500 * ns)
    # outside every module: unmatched; a module the census lacks: other
    assert red["unmatched"] == {"copy": pytest.approx(400 * ns),
                                "stray": pytest.approx(100 * ns)}
    assert red["unmatched_s"] == pytest.approx(500 * ns)
    assert red["other_programs"] == {
        "jit_alien": {"calls": 1, "seconds": pytest.approx(500 * ns)}}
    assert "jit_alien" not in red["programs"]
    # every second of busy is in exactly one place
    scoped = sum(v["seconds"] for p in red["programs"].values()
                 for v in p["scopes"].values())
    other = sum(o["seconds"] for o in red["other_programs"].values())
    assert red["busy_s"] == pytest.approx(4100 * ns)
    assert scoped + red["unmatched_s"] + other == pytest.approx(red["busy_s"])
    assert red["window_s"] == pytest.approx(9500 * ns)


def test_the_flat_table_tells_the_two_fusions_apart_and_the_window_clips():
    space = xplane.parse_xspace(_v5e_dump())
    rows = xplane.to_timeline(space, _CENSUS)
    assert rows[f"{DECODE}/fusion.3"]["scope"] == "mlp"
    assert rows[f"{CHUNK}/fusion.3"]["scope"] == "attention"
    assert rows[f"{CHUNK}/fusion.3"]["total_us"] == pytest.approx(1.0)
    assert rows["stray.1"]["module"] is None
    # the by-name table of old sums them (and the alien's is not in it)
    assert xplane.per_op_summary(space)["fusion.3"]["count"] == 3
    # clipped to the decode program's first half: the while is cut at 3000
    red = xplane.device_seconds(space, _CENSUS, window=(1000, 3000))
    dec = red["programs"][DECODE]
    assert set(red["programs"]) == {DECODE} and not red["other_programs"]
    assert dec["scopes"]["unscoped"]["seconds"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(1500e-9)
    # without a census every module is reported and nothing has a scope
    bare = xplane.device_seconds(space)
    assert set(bare["programs"]) == {DECODE, CHUNK, "jit_alien"}
    assert bare["unmatched_s"] == pytest.approx(bare["busy_s"])


# ------------------------------------------------------- the census's rules
@pytest.mark.parametrize("op_name,scope", [
    ("jit(llm_decode)/jit(main)/while/body/closed_call/attention/"
     "paged_attention/pallas_call", "attention/paged_attention"),
    ("jit(llm_decode)/while/body/closed_call/sampler/cond/branch_1_fun/"
     "cond/branch_1_fun/jit(sort)/sort", "sampler"),
    ("jit(f)/pjit/checkpoint/vmap(jit(g))/sparse_attention/sparse_select/"
     "reduce_max", "sparse_attention/sparse_select"),
    ("jit(llm_decode)/jit(_threefry_split)/LLMEngine._decode_fn.<locals>"
     ".llm_decode/while/body/closed_call/threefry2x32", "unscoped"),
    ("jit(f)/attention/bhsl,bhld->bshd/dot_general", "attention"),
    ("jit(f)/jvp(jit(g))/transpose(jvp(mlp))/mul", "unscoped"),
    ("jit(f)/remat/custom_vjp_call/shard_map/mlp/pallas_call", "mlp"),
    ("reduce_max", "unscoped"), ("", "unscoped"), (None, "unscoped"),
])
def test_the_scope_rule_strips_each_wrapper_it_lists(op_name, scope):
    assert census.scope_of(op_name) == scope


def test_a_fused_dot_gets_its_operations_and_a_while_body_its_rows():
    def f(x, w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)

        def body(c, _):
            with jax.named_scope("attention"):
                return jnp.sin(c @ w.T @ w), None

        h, _ = jax.lax.scan(body, h, None, length=3)
        return h.sum()

    compiled = jax.jit(f).lower(jnp.ones((8, 4)), jnp.ones((4, 16))).compile()
    rows = census.per_op_census(compiled)
    assert {r["module"] for r in rows} == {"jit_f"}
    whiles = [r for r in rows if r["opcode"] == "while"]
    assert len(whiles) == 1 and whiles[0]["bytes"] == 0
    body = [r for r in rows if r["computation"] != rows[0]["computation"]
            and r["scope"] == "attention"]
    assert sum(r["flops"] for r in body) == 2 * (2 * 8 * 16 * 4)
    assert [r["flops"] for r in rows if r["scope"] == "mlp"
            and r["flops"]] == [2 * 8 * 16 * 4]
    # a fusion's fused dots are summed onto its row, its scope is its
    # root's, and fused ops of two scopes mark it mixed
    txt = """HloModule jit_g, is_scheduled=true

%fused_computation (p0: bf16[32,64], p1: bf16[64,128]) -> bf16[32,128] {
  %p0 = bf16[32,64]{1,0} parameter(0)
  %p1 = bf16[64,128]{1,0} parameter(1)
  %convolution.1 = bf16[32,128]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(g)/mlp/dot_general"}
  ROOT %multiply.2 = bf16[32,128]{1,0} multiply(%convolution.1, %convolution.1), metadata={op_name="jit(g)/lm_head/mul"}
}

ENTRY %main.3 (a: bf16[32,64], b: bf16[64,128]) -> bf16[32,128] {
  %a = bf16[32,64]{1,0} parameter(0)
  %b = bf16[64,128]{1,0} parameter(1)
  ROOT %fusion.7 = bf16[32,128]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation
}
"""
    (row,) = census.per_op_census(txt)
    assert (row["module"], row["name"], row["opcode"]) \
        == ("jit_g", "fusion.7", "fusion")
    assert row["flops"] == 2 * 32 * 128 * 64
    assert row["bytes"] == 2 * (32 * 64 + 64 * 128 + 32 * 128)
    assert row["scope"] == "lm_head" and row["mixed"]
    assert census.by_module([row]) == {"jit_g": {"fusion.7": row}}


# --------------------------------------------- (b) the four model families
def _engines():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    def llama(**kw):
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128, tensor_parallel=False)
        model = LlamaForCausalLM(cfg)
        model.eval()
        return LLMEngine(model, max_batch_slots=3, max_seq_len=128,
                         page_size=8, num_pages=49, prefill_chunk=8, **kw)

    def nemotron_h():
        from test_nemotron_h import engine
        return engine()

    def minicpm_sala():
        from test_minicpm_sala import engine
        return engine()

    def deepseek_v3():
        from test_deepseek_v3 import engine
        return engine()

    return {"llama": llama, "nemotron_h": nemotron_h,
            "minicpm_sala": minicpm_sala, "deepseek_v3": deepseek_v3}


@pytest.mark.parametrize("family", ["llama", "nemotron_h", "minicpm_sala",
                                    "deepseek_v3"])
def test_an_engine_of_each_family_accounts_for_its_programs(family):
    eng = _engines()[family]()
    cen = eng.program_census()
    assert cen is eng.program_census()  # built once, kept
    # a Llama engine takes a chunk and the decode rows through one program
    assert {DECODE, "jit_llm_mixed" if family == "llama" else CHUNK} \
        <= set(cen)
    if family in ("llama", "deepseek_v3"):
        assert "jit_cow_copy_pages" in cen
    rows = [r for prog in cen.values() for r in prog.values()]
    # the decode program scans its tokens: the body's ops have rows
    whiles = [r for r in cen[DECODE].values() if r["opcode"] == "while"]
    assert whiles
    entry = next(iter(cen[DECODE].values()))["computation"]
    inner = [r for r in cen[DECODE].values() if r["computation"] != entry]
    assert len(inner) > len(whiles) \
        and any(r["scope"] != "unscoped" for r in inner)
    # no wrapper survives in a scope, and the work has names
    words = {w for r in rows for w in r["scope"].split("/")}
    assert not words & (census._WRAPPERS | {"body", "cond", "branch_0_fun",
                                            "branch_1_fun"})
    assert not any("(" in w or "<" in w or "," in w for w in words)
    total = sum(r["bytes"] for r in rows)
    scoped = sum(r["bytes"] for r in rows if r["scope"] != "unscoped")
    assert scoped >= 0.95 * total, sorted(
        ((r["bytes"], r["module"], r["name"]) for r in rows
         if r["scope"] == "unscoped"), reverse=True)[:8]


# ----------------------------- (c) never from __init__, warmup() or a tick
class _SpyJit:
    """A jitted function whose ahead-of-time lowerings are counted."""

    lowered = 0

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *a, **k):
        return self._fn(*a, **k)

    def lower(self, *a, **k):
        _SpyJit.lowered += 1
        return self._fn.lower(*a, **k)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def test_warmup_and_ticks_never_build_the_census(monkeypatch):
    real_jit = jax.jit
    monkeypatch.setattr(jax, "jit",
                        lambda *a, **k: _SpyJit(real_jit(*a, **k)))
    built = []
    real_census = census.per_op_census
    monkeypatch.setattr(census, "per_op_census",
                        lambda *a, **k: built.append(1) or real_census(*a, **k))
    _SpyJit.lowered = 0
    eng = _engines()["llama"]()
    eng.warmup()
    rng = np.random.default_rng(0)
    futs = [eng.submit(rng.integers(0, 256, 12 + i, dtype=np.int32),
                       max_new_tokens=12) for i in range(3)]
    for _ in range(10):
        eng.step()
    eng.run_until_complete()
    assert all(len(f.result(timeout=1)) == 12 for f in futs)
    assert _SpyJit.lowered == 0 and not built and eng._census is None
    stats = eng.stats()
    assert stats["device_time"] is None
    assert "census" not in json.dumps(stats, default=repr)
    # the spy does see a census being built
    cen = eng.program_census()
    assert _SpyJit.lowered == len(built) == len(cen) == 3
    assert "census" not in json.dumps(eng.stats(), default=repr)


# ------------------------------------------------------ (d) profile_device
def test_profile_device_publishes_and_survives_a_running_profiler(tmp_path):
    from paddle_tpu.observability.tracing import Tracer, TraceStore

    tracer = Tracer(store=TraceStore(capacity=64, sample_every=1))
    eng = _engines()["llama"](tracer=tracer)
    eng.warmup()
    eng.start()
    try:
        eng.program_census()  # the first call's seconds, before the work
        rng = np.random.default_rng(1)
        futs = [eng.submit(rng.integers(0, 256, 20, dtype=np.int32),
                           max_new_tokens=100) for _ in range(45)]
        red = eng.profile_device(0.5)
        dt = eng.stats()["device_time"]
        assert red is not None and dt["error"] is None
        assert dt["busy_s"] == red["busy_s"] > 0 and dt["window_s"] > 0
        assert dt["seconds"][DECODE]["attention"] > 0
        assert dt["seconds"][DECODE]["mlp"] > 0
        assert not dt["other_programs"]
        assert all("/" not in s for row in dt["seconds"].values() for s in row)
        gauge = REGISTRY.get("llm_device_seconds")
        assert gauge.labels(program=DECODE, scope="attention").value \
            == dt["seconds"][DECODE]["attention"]
        (trace,) = [t for t in tracer.store.trace_dicts()
                    if t["name"] == "profile_device"]
        assert "xplane_profile" in json.dumps(trace)
        # a profiler that is already running: recorded, not raised
        jax.profiler.start_trace(str(tmp_path / "outer"))
        try:
            assert eng.profile_device(0.05) is None
            assert eng.stats()["device_time"]["error"]
        finally:
            jax.profiler.stop_trace()
        assert all(len(f.result(timeout=60)) == 100 for f in futs)
    finally:
        eng.stop()
