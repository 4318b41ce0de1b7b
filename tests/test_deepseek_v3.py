"""A DeepSeek-V3-shaped decoder (multi-head latent attention, gated experts
with shared ones) at a small size on the CPU: the model and the serving
engine against the plain reference in the EXPANDED form, prefix hits with a
copy-on-write fork against a cold engine, absorbed against expanded, both
Pallas kernels in interpret mode against their dense fallbacks, the expert
shares against the uncut layer, the keys the code does not implement, and
the counters against counts worked by hand."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import serve_latent  # noqa: E402
from benchmark import weights_deepseek_v3 as W  # noqa: E402
from benchmark.reference import deepseek_v3_ref as ref  # noqa: E402
from paddle_tpu.inference import LLMEngine  # noqa: E402
from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,  # noqa: E402
                                           DeepseekV3ForCausalLM, DeepseekV3MoE)
from paddle_tpu.ops import latent_attention as la  # noqa: E402
from paddle_tpu.ops import moe_experts as moe_op  # noqa: E402

SEED = 11


def tiny_cfg(held=None, **kw):
    """Config-file keys at a toy size: one dense layer, two expert layers."""
    cfg = {
        "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "vocab_size": 256, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
        "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
        "n_shared_experts": 2, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "rope_theta": 10000,
        "rope_interleave": True, "rope_scaling": None, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 512, "torch_dtype": "float32", **kw}
    if held is not None:
        cfg["share"] = {"experts_held": list(held)}
    return cfg


ENGINE = dict(max_batch_slots=3, max_seq_len=128, page_size=8, num_pages=49,
              prefill_chunk=8)


def engine(cfg=None, **kw):
    model = serve_latent.build_model(cfg or tiny_cfg(), SEED)
    return LLMEngine(model, **{**ENGINE, **kw})


def prompt(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, 256, n, dtype=np.int32)


# (a) the model's cache-free forward against the reference ------------------------
def test_model_forward_in_one_pass_matches_the_reference():
    """float32 both sides, products at "highest".  The program runs the pass
    as one chunk over latent pages (40 queries: the absorbed form here), the
    reference materialises every head's keys and values: what is left is the
    order of float32 sums."""
    cfg = tiny_cfg()
    ids = np.stack([prompt(40, 1), prompt(40, 2)])
    model = serve_latent.build_model(cfg, SEED)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.forward(jnp.asarray(ids))._value)
        want = np.asarray(ref.full_logits(cfg, SEED, ids))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5)


# (b) chunked prefill, then decode through the latent pages -----------------------
@pytest.mark.parametrize("n", [5, 16, 37, 70], ids=lambda n: f"prompt{n}")
def test_engine_prefill_in_chunks_then_decode_matches_the_reference(n):
    """Chunks of 8 tokens into pages of 8, then 12 decode ticks in the
    absorbed form: the served token's logit lies within 1e-4 of the best
    logit of the reference's full forward pass (logits, not tokens: ties
    apart, it IS the best)."""
    cfg = tiny_cfg()
    eng = engine(cfg)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(eng.generate(prompt(n, n), max_new_tokens=12), np.int32)
        gaps, _ = ref.served_gap(cfg, SEED, [(prompt(n, n), out)], 128)
    assert len(gaps) == 12 and float(np.max(gaps)) < 1e-4
    # (h) the counters, by hand: 3 latent layers, 2 expert layers, top 2 of 8
    st = eng.stats()
    lat = st["latent_attention"]
    assert lat["layers"] == 3
    assert lat["prefill"] == {"layer_calls": 3 * n,
                              "context_tokens": 3 * n * (n + 1) // 2}
    assert lat["decode"] == {"layer_calls": 3 * 11, "context_tokens": 3 * sum(
        range(n + 1, n + 12))}
    moe = st["moe"]
    assert moe["expert_layers"] == 2 and moe["experts_held"] == 8
    assert moe["prefill"]["pairs_held"] == 2 * 2 * n
    assert moe["decode"]["pairs_held"] == 2 * 2 * 11
    assert moe["decode"]["pairs_absent"] == 0
    kinds = st["cache_kinds"]
    assert set(kinds) == {"paged_latent"} and kinds["paged_latent"]["layers"] == 3
    # 49 pages x 8 tokens x 128 lanes (32 + 8 padded) x 4 B x 3 layers
    assert kinds["paged_latent"]["bytes"] == 49 * 8 * 128 * 4 * 3


def test_a_masked_row_counts_nothing():
    """Two requests of unequal length on three slots: the idle slot and, once
    the short one is done, its slot too are computed but counted nowhere."""
    eng = engine()
    futs = [eng.submit(prompt(9, 1), max_new_tokens=3),
            eng.submit(prompt(12, 2), max_new_tokens=7)]
    eng.run_until_complete()
    assert [len(f.result()) for f in futs] == [3, 7]
    st = eng.stats()
    assert st["latent_attention"]["decode"]["layer_calls"] == 3 * (2 + 6)
    assert st["latent_attention"]["decode"]["context_tokens"] == 3 * (
        sum(range(10, 12)) + sum(range(13, 19)))
    assert st["moe"]["decode"]["pairs_held"] == 2 * 2 * (2 + 6)


# (c) a prefix hit gives the logits of a cold run ---------------------------------
def test_a_prefix_hit_with_a_forked_partial_page_gives_a_cold_runs_tokens():
    """A shared stretch of 21 tokens (two whole pages and 5 rows of a third)
    and tails that diverge INSIDE the partial page: whole pages are mapped,
    the partial one is forked copy-on-write, and every request reads what a
    cold engine reads, and what the reference puts first."""
    cfg = tiny_cfg()
    shared = prompt(21, 3)
    asks = [np.concatenate([shared, prompt(t, 40 + t)]) for t in (4, 6, 2, 11)]
    outs = {}
    for on in (False, True):
        eng = engine(cfg, prefix_cache=on, max_batch_slots=2)
        futs = [eng.submit(p, max_new_tokens=8) for p in asks]
        eng.run_until_complete()
        outs[on] = [np.asarray(f.result(), np.int32) for f in futs]
        if on:
            st = eng.stats()["prefix_cache"]
            assert st["hit_tokens"] > 0 and st["cow_copies"] > 0
            assert eng.stats()["llm_kv_pages_in_use"] == 0
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)
    with jax.default_matmul_precision("highest"):
        gaps, _ = ref.served_gap(cfg, SEED, list(zip(asks, outs[True])), 128)
    assert float(np.max(gaps)) < 1e-4


# (d) absorbed = expanded ----------------------------------------------------------
def _latent_case(rng, T, H=4, dn=16, dr=8, dv=16, dc=32, ps=8, M=6):
    W_ = la.pool_width(dc, dr)
    P = M + 2
    pool = np.zeros((P, ps, W_), np.float32)
    pool[1:, :, :dc + dr] = rng.standard_normal((P - 1, ps, dc + dr))
    tbl = rng.permutation(np.arange(1, P))[:M][None].astype(np.int32)
    w_kvb = rng.standard_normal((dc, H, dn + dv)).astype(np.float32) * 0.2
    q_nope = rng.standard_normal((T, H, dn)).astype(np.float32)
    q_rope = rng.standard_normal((T, H, dr)).astype(np.float32)
    return jnp.asarray(pool), jnp.asarray(tbl), jnp.asarray(w_kvb), \
        jnp.asarray(q_nope), jnp.asarray(q_rope)


def _expanded_by_hand(pool, tbl, w_kvb, q_nope, q_rope, ctx, dc, dr, dn, scale):
    """One query with context `ctx`: every head's keys and values
    materialised, in numpy."""
    lat = np.asarray(pool)[np.asarray(tbl)[0]].reshape(-1, pool.shape[-1])[:ctx]
    c, kr = lat[:, :dc], lat[:, dc:dc + dr]
    kv = np.einsum("kc,chd->khd", c, np.asarray(w_kvb))
    s = (np.einsum("hd,khd->hk", q_nope, kv[..., :dn])
         + np.einsum("hr,kr->hk", q_rope, kr)) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hk,khd->hd", p, kv[..., dn:])


def test_absorbed_equals_expanded_in_a_chunk_and_in_decode():
    rng = np.random.default_rng(0)
    dn, dr, dc, T, off = 16, 8, 32, 12, 19
    pool, tbl, w_kvb, q_nope, q_rope = _latent_case(rng, T)
    scale = 1.0 / (dn + dr) ** 0.5
    with jax.default_matmul_precision("highest"):
        a, e = (np.asarray(la.latent_chunk_attention(
            q_nope, q_rope, pool, tbl, jnp.int32(off), w_kvb, dc, scale,
            expanded=x)) for x in (False, True))
        np.testing.assert_allclose(a, e, atol=2e-5)
        for t in (0, 5, T - 1):
            want = _expanded_by_hand(pool, tbl, w_kvb, np.asarray(q_nope[t]),
                                     np.asarray(q_rope[t]), off + t + 1, dc, dr,
                                     dn, scale)
            np.testing.assert_allclose(e[t], want, atol=2e-5)
        # decode: the absorbed query through the gathered pass, W^V after
        n = jnp.asarray([off + 1], jnp.int32)
        q_abs = jnp.einsum("bhd,chd->bhc", q_nope[:1], w_kvb[..., :dn])
        o = la.latent_decode_attention(q_abs, q_rope[:1], pool, tbl, n, scale)
        got = np.einsum("bhc,chd->bhd", np.asarray(o), np.asarray(w_kvb)[..., dn:])
    np.testing.assert_allclose(got[0], e[0], atol=2e-5)


def test_the_form_of_a_chunk_is_chosen_by_arithmetic():
    """At the published sizes 256 queries are less work expanded (a key is
    expanded once for all of them), a handful are less work absorbed."""
    sizes = (32, 512, 64, 128, 128)
    assert la.pair_ops(*sizes) == (69_632, 20_480, 2 * 512 * 8192)
    assert la.expanded_wins(256, *sizes) and la.expanded_wins(171, *sizes)
    assert not la.expanded_wins(170, *sizes) and not la.expanded_wins(8, *sizes)


# (e) the kernels in interpret mode against their fallbacks -----------------------
def test_the_latent_kernel_matches_the_gathered_pass():
    """Tile-aligned sizes (8 heads, latent 128 + rope 64 in 256 lanes, pages of
    16): rows at a long context, inside one page, at context 1 and idle.
    float32 pools: the online soft-max against one pass differs by the order
    of the sums."""
    rng = np.random.default_rng(1)
    B, H, dc, dr, ps, P, M = 4, 8, 128, 64, 16, 60, 14
    Wd = la.pool_width(dc, dr)
    assert Wd == 256
    pool = np.zeros((P, ps, Wd), np.float32)
    pool[:, :, :dc + dr] = rng.standard_normal((P, ps, dc + dr))
    pool = jnp.asarray(pool)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[:B * M].reshape(B, M), jnp.int32)
    q_abs = jnp.asarray(rng.standard_normal((B, H, dc)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, H, dr)), jnp.float32)
    n = jnp.asarray([200, 7, 1, 0], jnp.int32)
    out = {k: np.asarray(la.latent_decode_attention(
        q_abs, q_rope, pool, tbl, n, 0.1, use_kernel=k, interpret=True))
        for k in (False, True)}
    np.testing.assert_allclose(out[True], out[False], atol=2e-5)
    assert np.all(out[True][3] == 0) and np.abs(out[True][0]).max() > 0.01
    # and the gathered pass against a soft-max over the row's own tokens
    lat = np.asarray(pool)[np.asarray(tbl)[0]].reshape(-1, Wd)[:200]
    q = np.concatenate([np.asarray(q_abs[0]), np.asarray(q_rope[0])], -1)
    s = q @ lat[:, :dc + dr].T * 0.1
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(out[False][0], (p / p.sum(-1, keepdims=True))
                               @ lat[:, :dc], atol=2e-5)


def test_the_dispatch_of_the_latent_pass_is_counted():
    from paddle_tpu.inference.llm_server import _attn_dispatch_series

    def taken(path):
        return sum(n for labels, n in _attn_dispatch_series() if labels[0] == path)

    before = taken("latent_dense"), taken("latent_kernel")
    engine().generate(prompt(6, 1), max_new_tokens=3)
    # the tiny sizes are off the tile (32 + 8 values a row): the fallback
    assert taken("latent_dense") > before[0] and taken("latent_kernel") == before[1]


@pytest.mark.parametrize("use_kernel", [False, True], ids=["fallback", "interpret"])
def test_the_gated_experts_match_a_loop_over_the_pairs(use_kernel):
    rng = np.random.default_rng(2)
    T, h, F, E, K, lo = 24, 128, 16, 6, 2, 2
    x = rng.standard_normal((T, h)).astype(np.float32)
    wg, w1, w2 = (rng.standard_normal((E, F, h)).astype(np.float32) * 0.1
                  for _ in range(3))
    expert = np.stack([rng.permutation(10)[:K] for _ in range(T)]).astype(np.int32)
    weight = rng.random((T, K)).astype(np.float32)
    real = np.arange(T) < 20
    out, counts = moe_op.moe_experts(
        jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2), jnp.asarray(expert),
        jnp.asarray(weight), lo, real=jnp.asarray(real), use_kernel=use_kernel,
        interpret=True, w_gate=jnp.asarray(wg))
    want = np.zeros((T, h), np.float32)
    by_hand = np.zeros(E + 1, np.int64)
    for t in range(T):
        for k in range(K):
            e = expert[t, k] - lo
            if 0 <= e < E:
                g = x[t] @ wg[e].T
                want[t] += weight[t, k] * ((g / (1 + np.exp(-g)) * (x[t] @ w1[e].T)) @ w2[e])
                by_hand[e] += real[t]
    by_hand[E] = (by_hand[:E] > 0).sum()
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), by_hand)


def test_the_ungated_form_is_the_program_it_was():
    """The op without `w_gate` lowers to the kernel named `moe_experts` with
    two matrices an expert; with it, to `moe_glu_experts` with three."""
    x = jnp.zeros((8, 128), jnp.float32)
    w = jnp.zeros((2, 16, 128), jnp.float32)
    idx, wt = jnp.zeros((8, 1), jnp.int32), jnp.ones((8, 1), jnp.float32)
    f = lambda g: jax.jit(lambda: moe_op.moe_experts(  # noqa: E731
        x, w, w, idx, wt, 0, use_kernel=True, interpret=False, w_gate=g)
    ).trace().jaxpr
    plain, gated = str(f(None)), str(f(w))
    assert "name=moe_experts" in plain and "moe_glu_experts" not in plain
    assert "name=moe_glu_experts" in gated


# (f) the expert shares against the uncut layer ------------------------------------
def test_the_shares_routed_parts_plus_the_shared_experts_once_give_the_uncut_layer():
    """Guide section 4: experts 0-3 on one device, 4-7 on the other; the two
    routed parts, with the shared experts and the router counted once, equal
    what the reference gives for the whole layer of 8."""
    whole = tiny_cfg()
    lw = {k: np.asarray(v, np.float32)
          for k, v in W.make_layer(W.seed_key(SEED), whole, 1, "E").items()}
    u = 8 * np.asarray(jax.random.normal(jax.random.PRNGKey(3), (24, 64)), np.float32)
    s = W.sizes(whole)
    want = ref.experts(u, lw, s, (0, 8)) + ref.shared_experts(u, lw)
    parts, shared, routes = [], None, []
    for lo, hi in ((0, 4), (4, 8)):
        layer = DeepseekV3MoE(DeepseekV3Config.tiny(experts_held=(lo, hi)))
        for name, leaf in (("gate_weight", "router"),
                           ("e_score_correction_bias", "router_bias")):
            getattr(layer, name).set_value(jnp.asarray(lw[leaf]))
        for name, leaf in (("gate_proj", "sgate"), ("up_proj", "sup"),
                           ("down_proj", "sdown")):
            getattr(layer.shared_experts, name).set_value(jnp.asarray(lw[leaf]))
        for name, leaf in (("experts_gate", "egate"), ("experts_up", "eup"),
                           ("experts_down", "edown")):
            getattr(layer, name).set_value(jnp.asarray(lw[leaf][lo:hi]))
        idx, w = layer.route(jnp.asarray(u))
        routes.append(np.asarray(idx))
        out, counts = layer(jnp.asarray(u)[None], None)
        routed, _ = moe_op.moe_experts(
            jnp.asarray(u), layer.experts_up._value, layer.experts_down._value,
            idx, w, lo, w_gate=layer.experts_gate._value)
        parts.append(np.asarray(routed))
        # the same share through the reference
        np.testing.assert_allclose(parts[-1], ref.experts(
            u, {**lw, **{k: lw[k][lo:hi] for k in ("egate", "eup", "edown")}}, s,
            (lo, hi)), atol=1e-4)
        assert int(counts[:-1].sum()) == int(((idx >= lo) & (idx < hi)).sum())
        shared = np.asarray(out)[0] - parts[-1]
    np.testing.assert_array_equal(routes[0], routes[1])   # one router, all 8
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want, atol=2e-4)


def test_the_router_is_the_one_nemotron_routes_through():
    from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHMoE

    rng = np.random.default_rng(4)
    a = NemotronHMoE(NemotronHConfig.tiny(num_hidden_layers=1,
                                          hybrid_override_pattern="E",
                                          routed_scaling_factor=2.448))
    b = DeepseekV3MoE(DeepseekV3Config.tiny())
    gw = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.06, 0.06, 8), jnp.float32)
    for layer in (a, b):
        layer.gate_weight.set_value(gw)
        layer.e_score_correction_bias.set_value(bias)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    (ia, wa), (ib, wb) = a.route(x), b.route(x)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
    # the bias chooses and does not weigh; the weights add up to the scaling
    np.testing.assert_allclose(np.asarray(wb).sum(-1), 2.448, rtol=1e-5)
    s = np.asarray(jax.nn.sigmoid(x @ gw))
    top = np.argsort(-(s + np.asarray(bias)), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(ib), -1), np.sort(top, -1))


# (g) what the code does not implement is refused ----------------------------------
@pytest.mark.parametrize("kw", [
    dict(q_lora_rank=1536), dict(n_group=8, topk_group=4), dict(topk_group=2),
    dict(rope_scaling={"type": "yarn", "factor": 40}), dict(rope_interleave=False),
    dict(scoring_func="softmax"), dict(topk_method="greedy"),
    dict(num_key_value_heads=2), dict(hidden_act="gelu"), dict(moe_layer_freq=2),
    dict(attention_bias=True), dict(tie_word_embeddings=True),
    dict(experts_held=(4, 12)),
], ids=lambda kw: next(iter(kw)))
def test_keys_the_code_does_not_implement_raise(kw):
    with pytest.raises(ValueError):
        DeepseekV3Config.tiny(**kw)


@pytest.mark.parametrize("kw,word", [
    (dict(cache_dtype="int8"), "int8"),
    (dict(host_cache_pages=4), "host_cache_pages"),
    (dict(spec_k=2), "spec_k"),
], ids=["int8_pages", "kv_tiers", "speculation"])
def test_engine_options_that_cannot_serve_a_latent_pool_are_refused(kw, word):
    model = serve_latent.build_model(tiny_cfg())
    with pytest.raises(ValueError, match=word):
        LLMEngine(model, **{**ENGINE, **kw})


def test_parameters_are_created_in_the_configured_dtype():
    model = serve_latent.build_model(dict(tiny_cfg(), torch_dtype="bfloat16"))
    kinds = {n.split(".")[-1]: str(p._value.dtype) for n, p in model.named_parameters()}
    assert {k for k, v in kinds.items() if v == "float32"} == {"e_score_correction_bias"}
    assert {v for k, v in kinds.items() if k != "e_score_correction_bias"} == {"bfloat16"}
    assert isinstance(model, DeepseekV3ForCausalLM)
    assert model.num_params == W.n_params(tiny_cfg())


def test_interleaved_rope_rotates_adjacent_pairs():
    x = np.random.default_rng(5).standard_normal((2, 3, 4, 8)).astype(np.float32)
    pos = np.array([[0, 1, 2], [7, 8, 9]], np.int32)
    got = np.asarray(la.rope_interleaved(jnp.asarray(x), jnp.asarray(pos), 10000.0))
    for i in range(4):
        ang = pos[..., None] * 10000.0 ** (-2 * i / 8)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        np.testing.assert_allclose(got[..., 2 * i], a * np.cos(ang) - b * np.sin(ang),
                                   atol=1e-5)
        np.testing.assert_allclose(got[..., 2 * i + 1], b * np.cos(ang) + a * np.sin(ang),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.rope_pairs(
        jnp.asarray(x[1]), jnp.asarray(pos[1]), 10000.0)), got[1], atol=1e-5)
