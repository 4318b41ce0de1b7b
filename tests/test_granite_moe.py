"""Granite-4.0-H with routed experts (Small's layer: a gated mixture of experts
beside the shared MLP in every Mamba-2 and attention layer, softmax over the
chosen logits) at a small size on the CPU: the serving engine against the
plain reference, the router's form, two shares of the experts against the
uncut layer, the engine's expert counters through recurrent and paged layers,
the state kernel at 128 heads, and Micro (no experts) built as before."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import serve_granite, serve_granite_moe  # noqa: E402
from benchmark import weights_granite_hybrid as W_micro  # noqa: E402
from benchmark import weights_granite_moe as W  # noqa: E402
from benchmark.reference import granite_moe_ref as ref  # noqa: E402
from paddle_tpu.inference import LLMEngine  # noqa: E402
from paddle_tpu.inference import llm_server  # noqa: E402
from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,  # noqa: E402
                                              GraniteHybridMoE)
from paddle_tpu.ops import moe_experts as moe_op  # noqa: E402
from paddle_tpu.ops import ssm_update as ssm_op  # noqa: E402

SEED = 13
ENGINE = dict(max_batch_slots=3, max_seq_len=128, page_size=16, num_pages=25,
              prefill_chunk=16)
TYPES = ["mamba", "attention", "mamba", "mamba"]


def tiny_cfg(held=(0, 8), **kw):
    """Small's config-file keys at a toy size: 8 router experts of width 128,
    top 2, `held` of them here; layers wide enough that their outputs, not
    the embedding, lead the residual stream (narrower ones leave the tied
    head echoing its input token, and a token comparison then sees nothing)."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-small-l10-ep2.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, vocab_size=256, num_hidden_layers=len(TYPES),
               layer_types=TYPES, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=16, mamba_d_head=32, mamba_d_state=16,
               mamba_chunk_size=8, shared_intermediate_size=1024,
               intermediate_size=128, num_local_experts=held[1] - held[0],
               num_experts_per_tok=2, torch_dtype="float32",
               share={"experts_held": list(held), "router_experts": 8})
    cfg.update(kw)
    return cfg


def engine(cfg=None, **kw):
    model = serve_granite_moe.build_model(cfg or tiny_cfg(), SEED)
    return LLMEngine(model, kv_layout="paged", **{**ENGINE, **kw})


def prompt(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, 256, n, dtype=np.int32)


def ref_logits(cfg, toks, pad_to=64):
    toks = np.atleast_2d(toks)
    padded = np.pad(toks, ((0, 0), (0, pad_to - toks.shape[1])))
    return np.asarray(ref.full_logits(cfg, SEED, padded))[:, :toks.shape[1]]


# ------------------------------------------------------ model vs reference
@pytest.mark.parametrize("held", [(0, 8), (0, 4)], ids=["all_held", "half_held"])
def test_model_forward_without_cache_matches_the_reference(held):
    cfg = tiny_cfg(held)
    model = serve_granite_moe.build_model(cfg, SEED)
    toks = np.stack([prompt(24, 1), prompt(24, 2)])
    got = np.asarray(model(jnp.asarray(toks))._value)
    want = ref_logits(cfg, toks)
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert (want.argmax(-1) == toks).mean() < 0.5   # the layers, not an echo


@pytest.mark.parametrize("n", [12, 40], ids=["one_chunk", "three_chunks"])
def test_engine_prefill_then_decode_matches_the_reference(monkeypatch, n):
    """One chunk of 16, or chunks of 16, 16 and 8 with the state carried; the
    first token's logits equal the reference's full pass and every served
    token is its own best.  Every layer has experts, so the counters take
    pairs from the three recurrent layers AND the paged one."""
    cfg = tiny_cfg((0, 4))
    rows = []
    orig = llm_server.LLMEngine._host_select
    monkeypatch.setattr(llm_server.LLMEngine, "_host_select",
                        lambda self, row, req: rows.append(np.array(row)) or orig(self, row, req))
    eng = engine(cfg)
    p = prompt(n, 3)
    out = np.asarray(eng.generate(p, max_new_tokens=12))
    np.testing.assert_allclose(rows[0], ref_logits(cfg, p)[0, -1], atol=2e-5, rtol=0)
    gaps, _ = ref.served_gap(cfg, SEED, [(p, out)], 64)
    assert gaps.max() < 1e-4
    st = eng.stats()
    kinds = eng.model.cache_kinds()
    assert [k.kind for k in kinds] == ["recurrent", "paged_kv", "recurrent", "recurrent"]
    assert all(k.experts_held == 4 and k.top_k == 2 for k in kinds)
    chunks = -(-n // 16)
    moe = st["moe"]
    assert moe["prefill"]["layer_calls"] == chunks * 4
    assert moe["decode"]["layer_calls"] == 11 * 4
    assert moe["prefill"]["pairs_held"] + moe["prefill"]["pairs_absent"] == n * 2 * 4
    assert moe["decode"]["pairs_held"] + moe["decode"]["pairs_absent"] == 11 * 2 * 4
    assert 0 < moe["decode"]["pairs_held"] < 11 * 2 * 4
    assert 0 < moe["decode"]["experts_touched"] <= moe["decode"]["pairs_held"]
    assert st["recurrent_state"]["carried_chunks"] == chunks - 1


def test_the_paged_layers_counts_reach_the_engine_and_not_its_pools():
    """One attention layer alone holds experts' pairs: every pair the
    counters see came through the paged_kv layer, and the pools it keeps
    are the pools alone."""
    cfg = tiny_cfg((0, 8), num_hidden_layers=2, layer_types=["attention", "mamba"])
    eng = engine(cfg)
    out = eng.generate(prompt(20, 4), max_new_tokens=5)
    assert len(out) == 5
    moe = eng.stats()["moe"]
    # all experts held: every pair is held, 2 a row a layer
    assert moe["prefill"]["pairs_held"] == 20 * 2 * 2 and moe["prefill"]["pairs_absent"] == 0
    assert moe["decode"]["pairs_held"] == 4 * 2 * 2
    assert len(eng.caches[0]) == 2 and eng.caches[0][0].ndim == 4   # k and v pools


# ------------------------------------------------------------- the router
def test_the_router_is_a_softmax_over_the_chosen_logits_and_the_other_form_stays():
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (9, 32))
    wr = jax.random.normal(k[1], (32, 12))
    idx, g = moe_op.route_softmax(x, wr, 3)
    logits = np.asarray(x, np.float64) @ np.asarray(wr, np.float64)
    want_idx = np.argsort(-logits, axis=-1)[:, :3]
    top = np.take_along_axis(logits, want_idx, -1)
    want_g = np.exp(top - top.max(-1, keepdims=True))
    want_g /= want_g.sum(-1, keepdims=True)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(g), want_g, atol=1e-6)
    assert g.dtype == jnp.float32 and idx.dtype == jnp.int32
    # the sigmoid form (Nemotron-H, DeepSeek-V3) as it was: bias chooses,
    # the scores weigh, normalised and scaled
    bias = jax.random.normal(k[2], (12,)) * 0.1
    idx2, w2 = moe_op.route(x, wr, bias, 3, True, 2.5)
    s = 1 / (1 + np.exp(-logits))
    pick = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :3]
    w = np.take_along_axis(s, pick, -1)
    np.testing.assert_array_equal(np.asarray(idx2), pick)
    np.testing.assert_allclose(np.asarray(w2), w / w.sum(-1, keepdims=True) * 2.5,
                               atol=1e-5)


# --------------------------------------------------------- the share test
def test_two_shares_of_the_experts_plus_the_shared_expert_once_give_the_uncut_layer():
    """Guide section 4: experts 0-3 on one chip, 4-7 on the other; the two
    routed parts and the shared expert counted once equal the reference's
    whole layer of 8, in the reference and in the program."""
    whole = tiny_cfg((0, 8))
    key = W.seed_key(SEED)
    lw = {k: np.asarray(v, np.float32)
          for k, v in W.make_layer(key, whole, 0, "mamba").items()}
    v = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (24, 128)), np.float32)
    s = W.sizes(whole)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(v, lw, s) + ref.mlp(v, lw, s))
        shared = np.asarray(ref.mlp(v, lw, s))
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        half = tiny_cfg((lo, hi))
        hw = {k: np.asarray(x, np.float32)
              for k, x in W.make_layer(key, half, 0, "mamba").items()}
        # a share's experts are the uncut layer's, drawn by their own index
        for e in W.EXPERTS:
            np.testing.assert_array_equal(hw[e], lw[e][lo:hi])
        with jax.default_matmul_precision("highest"):
            r = np.asarray(ref.experts(v, hw, W.sizes(half)))
        layer = GraniteHybridMoE(GraniteHybridConfig.tiny(
            hidden_size=128, num_local_experts=8, num_experts_per_tok=2,
            intermediate_size=128, experts_held=(lo, hi)))
        layer.gate_weight.set_value(jnp.asarray(hw["router"]))
        for name, leaf in (("experts_gate", "e_gate"), ("experts_up", "e_up"),
                           ("experts_down", "e_down")):
            getattr(layer, name).set_value(jnp.asarray(hw[leaf]))
        with jax.default_matmul_precision("highest"):
            got, counts = layer(jnp.asarray(v)[None], None)
        np.testing.assert_allclose(np.asarray(got)[0], r, atol=2e-5)
        idx, _ = ref.route(jnp.asarray(v), hw, W.sizes(half))
        assert int(counts[:-1].sum()) == int(((idx >= lo) & (idx < hi)).sum())
        parts.append(r)
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want, atol=2e-5)


@pytest.mark.parametrize("form", ["softmax_all", "held_renorm"])
def test_each_wrong_gate_form_moves_the_layer(form):
    """The two router controls of the cell give another layer output."""
    cfg = tiny_cfg((0, 4))
    s = W.sizes(cfg)
    lw = {k: np.asarray(x, np.float32)
          for k, x in W.make_layer(W.seed_key(SEED), cfg, 0, "mamba").items()}
    v = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (24, 128)), np.float32)
    with jax.default_matmul_precision("highest"):
        sound = np.asarray(ref.experts(v, lw, s))
        other = np.asarray(ref.experts(v, lw, s, form=form))
    assert np.abs(sound - other).max() > 0.2 * np.abs(sound).max() > 0


# ------------------------------------------------ the state kernel at 128 heads
def test_the_state_kernel_takes_a_slot_in_row_tiles_at_128_heads():
    """128 heads of 64 at one group: [64, 128, 128] float32 a slot (4 MiB),
    cut into two tiles of rows; 64 heads and Nemotron's 8 groups stay one
    block a slot.  The tiled kernel (interpret mode) equals the dense pass."""
    R, N, L = ssm_op.state_shape(128, 64, 128, 1)
    assert (R, N, L) == (64, 128, 128) and ssm_op._row_tiles(R, N, L, 1) == 2
    assert ssm_op._row_tiles(*ssm_op.state_shape(64, 64, 128, 1), 1) == 1
    assert ssm_op._row_tiles(*ssm_op.state_shape(64, 64, 128, 8), 8) == 1
    k = jax.random.split(jax.random.PRNGKey(6), 5)
    B = 2
    state = jax.random.normal(k[0], (B, R, N, L))
    xdt = jax.random.normal(k[1], (B, R, L))
    da = jax.random.uniform(k[2], (B, R, L))
    bm, cm = (jax.random.normal(kk, (B, 1, N)) for kk in k[3:])
    got_s, got_y = ssm_op.state_pass(state, xdt, da, bm, cm, use_kernel=True,
                                     interpret=True)
    want_s, want_y = ssm_op._state_dense(state, xdt, da, bm, cm)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4)


# ------------------------------------------------ Micro, with no experts
def test_micro_builds_the_same_parameters_and_no_expert_work():
    """num_local_experts 0: the parameter names are the benchmark's Micro
    leaves one for one, no layer reports experts, and neither serving
    program holds a router or an expert kernel."""
    with open(os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, vocab_size=256, num_hidden_layers=4, layer_types=TYPES,
               num_attention_heads=2, num_key_value_heads=2, mamba_n_heads=8,
               mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
               shared_intermediate_size=256, torch_dtype="float32")
    model = serve_granite.build_model(cfg, SEED)
    names = {n for n, _ in model.named_parameters()}
    assert names == {W_micro.program_name(n) for n in W_micro.leaf_shapes(cfg)}
    assert not any("block_sparse_moe" in n for n in names)
    assert all(k.experts_held == 0 and k.top_k == 0 for k in model.cache_kinds())
    eng = LLMEngine(model, kv_layout="paged", **ENGINE)
    assert eng.stats()["moe"] is None
    for jit, args, _ in eng._programs():
        txt = jit.lower(*args).as_text()
        assert "moe_router" not in txt and "moe_glu_experts" not in txt, jit.__name__


# ------------------------------------------------------------ what is refused
@pytest.mark.parametrize("kw,word", [
    (dict(num_local_experts=8, num_experts_per_tok=9, intermediate_size=64),
     "num_experts_per_tok"),
    (dict(num_local_experts=8, num_experts_per_tok=2, intermediate_size=0),
     "intermediate_size"),
    (dict(num_local_experts=8, num_experts_per_tok=2, intermediate_size=64,
          experts_held=(4, 9)), "experts_held"),
    (dict(experts_held=(0, 4)), "experts_held"),
], ids=["top_k_over_experts", "no_expert_width", "held_outside", "held_without_experts"])
def test_the_config_refuses_an_expert_layer_it_cannot_build(kw, word):
    with pytest.raises(ValueError, match=word):
        GraniteHybridConfig.tiny(**kw)
