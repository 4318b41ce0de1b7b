"""Nemotron-H (Mamba-2 + experts + attention) at a small size on the CPU:
the serving engine against the plain reference, the expert shares against the
uncut layer, slot reuse and preemption against a fresh engine, each kernel
against dense jnp, and what the engine refuses for recurrent state."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import serve_hybrid  # noqa: E402
from benchmark import weights_nemotron_h as W  # noqa: E402
from benchmark.reference import nemotron_h_ref as ref  # noqa: E402
from paddle_tpu.inference import LLMEngine  # noqa: E402
from paddle_tpu.inference import llm_server  # noqa: E402
from paddle_tpu.ops import moe_experts as moe_op  # noqa: E402
from paddle_tpu.ops import ssm_update as ssm_op  # noqa: E402

SEED = 7


def tiny_cfg(held=(0, 4), pattern="MEM*E"):
    """Config-file keys at a toy size: 8 published experts, `held` of them here."""
    return {
        "hidden_size": 64, "vocab_size": 256, "num_hidden_layers": len(pattern),
        "hybrid_override_pattern": pattern, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "conv_kernel": 4, "chunk_size": 8, "n_routed_experts": held[1] - held[0],
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
        "torch_dtype": "float32",
        "share": {"experts_held": list(held), "router_experts": 8},
    }


ENGINE = dict(max_batch_slots=3, max_seq_len=128, page_size=16, num_pages=25,
              prefill_chunk=16)


def engine(cfg=None, **kw):
    cfg = cfg or tiny_cfg()
    model = serve_hybrid.build_model(cfg, SEED)
    return LLMEngine(model, kv_layout="paged", **{**ENGINE, **kw})


def prompt(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, 256, n, dtype=np.int32)


def served_gaps(cfg, pairs, pad_to=64):
    gaps, _ = ref.served_gap(cfg, SEED, pairs, pad_to)
    return gaps


# (a) ------------------------------------------------------------------------
def test_engine_prefill_in_three_chunks_then_decode_matches_the_reference(monkeypatch):
    """40 prompt tokens = chunks of 16, 16 and 8 (the last padded to 16), then
    12 decode ticks through the caches: the first token's logits equal the
    reference's full forward pass, and every served token is the reference's
    own best (float32: a gap is rounding)."""
    cfg = tiny_cfg()
    rows = []
    orig = llm_server.LLMEngine._host_select
    monkeypatch.setattr(llm_server.LLMEngine, "_host_select",
                        lambda self, row, req: rows.append(np.array(row)) or orig(self, row, req))
    eng = engine(cfg)
    p = prompt(40)
    out = np.asarray(eng.generate(p, max_new_tokens=12))
    assert out.shape == (12,)
    logits = np.asarray(ref.full_logits(cfg, SEED, np.pad(p, (0, 24))[None]))[0]
    np.testing.assert_allclose(rows[0], logits[39], atol=2e-4, rtol=0)
    gaps = served_gaps(cfg, [(p, out)])
    assert gaps.max() < 1e-3
    st = eng.stats()
    assert st["cache_kinds"]["paged_kv"]["layers"] == 1
    assert st["cache_kinds"]["recurrent"]["layers"] == 2
    assert st["cache_kinds"]["none"] == {"layers": 2, "bytes": 0}
    assert st["recurrent_state"] == {
        "bytes": 2 * 3 * (4 * 8 * 16 * 4 + 3 * (32 + 2 * 2 * 16) * 4),
        "slots": 3, "layers": 2, "checkpoints": None,  # no prefix cache: no pool
        "carried_chunks": 2}  # the 2nd and 3rd chunk resumed the 1st's state
    # 40 prompt tokens and 11 decode rows through 2 expert layers, 2 of 8 each
    moe = st["moe"]
    assert moe["prefill"]["layer_calls"] == 3 * 2 and moe["decode"]["layer_calls"] == 11 * 2
    assert moe["prefill"]["pairs_held"] + moe["prefill"]["pairs_absent"] == 40 * 2 * 2
    assert moe["decode"]["pairs_held"] + moe["decode"]["pairs_absent"] == 11 * 2 * 2
    assert 0 < moe["decode"]["pairs_held"] < 11 * 2 * 2
    assert 0 < moe["decode"]["experts_touched"] <= moe["decode"]["pairs_held"]


def test_model_forward_without_cache_matches_the_reference():
    cfg = tiny_cfg()
    model = serve_hybrid.build_model(cfg, SEED)
    toks = np.stack([prompt(24, 1), prompt(24, 2)])
    got = np.asarray(model(jnp.asarray(toks))._value)
    want = np.asarray(ref.full_logits(cfg, SEED, np.pad(toks, ((0, 0), (0, 40)))))[:, :24]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


# (b) ------------------------------------------------------------------------
def test_the_shares_routed_parts_plus_the_shared_expert_once_give_the_uncut_layer():
    """Guide section 4: experts 0-3 on one device, 4-7 on the other; the two
    routed parts and the shared expert counted once equal what the reference
    gives for the whole layer of 8."""
    whole = tiny_cfg(held=(0, 8), pattern="E")
    key = W.seed_key(SEED)
    lw = {k: np.asarray(v, np.float32)
          for k, v in W.make_layer(key, whole, 0, "E").items()}
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (24, 64)), np.float32)
    s = W.sizes(whole)
    want = ref.experts(u, lw, s, (0, 8)) + ref.shared_expert(u, lw)
    from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHMoE

    parts, shared = [], None
    for lo, hi in ((0, 4), (4, 8)):
        nc = NemotronHConfig.tiny(num_hidden_layers=1, hybrid_override_pattern="E",
                                  experts_held=(lo, hi))
        layer = NemotronHMoE(nc)
        for name, leaf in (("gate_weight", "router"), ("shared_up", "shared_up"),
                           ("e_score_correction_bias", "router_bias"),
                           ("shared_down", "shared_down")):
            getattr(layer, name).set_value(jnp.asarray(lw[leaf]))
        layer.experts_up.set_value(jnp.asarray(lw["up"][lo:hi]))
        layer.experts_down.set_value(jnp.asarray(lw["down"][lo:hi]))
        idx, w = layer.route(jnp.asarray(u))
        routed, counts = moe_op.moe_experts(
            jnp.asarray(u), layer.experts_up._value, layer.experts_down._value,
            idx, w, lo)
        parts.append(np.asarray(routed))
        # the same layer through the reference, given this share
        np.testing.assert_allclose(
            parts[-1], ref.experts(u, {**lw, "up": lw["up"][lo:hi],
                                       "down": lw["down"][lo:hi]}, s, (lo, hi)),
            atol=1e-4)
        assert int(counts[:-1].sum()) == int(((idx >= lo) & (idx < hi)).sum())
        out, _ = layer(jnp.asarray(u)[None], None)
        shared = np.asarray(out)[0] - parts[-1]
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want, atol=2e-4)


# (c) ------------------------------------------------------------------------
def test_a_slot_that_served_another_request_first_gives_a_fresh_engines_tokens():
    eng = engine(max_batch_slots=1)
    eng.generate(prompt(30, 5), max_new_tokens=9)      # leaves state in slot 0
    again = np.asarray(eng.generate(prompt(21, 6), max_new_tokens=10))
    fresh = np.asarray(engine(max_batch_slots=1).generate(prompt(21, 6), max_new_tokens=10))
    np.testing.assert_array_equal(again, fresh)


def test_a_preempted_and_requeued_request_gives_a_fresh_engines_tokens():
    """4 pages of 16 tokens for two requests that grow to 3 pages each: one is
    preempted mid-decode, requeued, and recomputed from a zeroed state."""
    from paddle_tpu.observability import metrics

    eng = engine(max_batch_slots=2, num_pages=5)
    before = metrics.REGISTRY.get("llm_page_preemptions_total").value
    futs = [eng.submit(prompt(20, 10 + i), max_new_tokens=22) for i in range(2)]
    eng.run_until_complete()
    got = [np.asarray(f.result()) for f in futs]
    assert metrics.REGISTRY.get("llm_page_preemptions_total").value > before
    for i in range(2):
        fresh = np.asarray(engine(max_batch_slots=1).generate(
            prompt(20, 10 + i), max_new_tokens=22))
        np.testing.assert_array_equal(got[i], fresh)
    gaps = served_gaps(tiny_cfg(), [(prompt(20, 10 + i), got[i]) for i in range(2)])
    assert gaps.max() < 1e-3


# (d) ------------------------------------------------------------------------
SMALL = dict(B=3, H=16, P=8, N=128, G=2)
PUBLISHED = dict(B=3, H=64, P=64, N=128, G=8)  # a slot's shape as published


def _rows(S, R):
    """[B, H, P, N] -> the layout a slot keeps: row r holds heads r*k..r*k+k-1
    side by side on its lanes, n on the sublanes."""
    B, H, P, N = S.shape
    k = H // R
    out = jnp.einsum("brjpn->brnjp", S.reshape(B, R, k, P, N)).reshape(B, R, N, k * P)
    assert out[B - 1, R - 1, 3, (k - 1) * P + 2] == S[B - 1, H - 1, 2, 3]
    return out


def _ssm_case(B, H, P, N, G):
    K = 4
    C = H * P + 2 * G * N
    k = jax.random.split(jax.random.PRNGKey(0), 9)
    kw = dict(conv_weight=jax.random.normal(k[4], (K, C)) * 0.3,
              conv_bias=jax.random.normal(k[5], (C,)) * 0.1,
              a_log=jnp.log(jnp.linspace(1, 16, H)),
              dt_bias=jax.random.normal(k[6], (H,)), d_skip=jnp.ones(H),
              groups=G, n_state=N)
    R = ssm_op.state_shape(H, P, N, G)[0]
    state = _rows(jax.random.normal(k[0], (B, H, P, N)), R)
    assert state.shape[1:] == ssm_op.state_shape(H, P, N, G)
    conv = jax.random.normal(k[1], (B, (K - 1) * C))
    return (B, H, C), state, conv, k, kw


def test_the_published_state_fills_whole_vregs_and_nothing_is_padded():
    assert ssm_op.state_shape(64, 64, 128, 8) == (32, 128, 128)
    assert ssm_op.state_shape(4, 8, 16, 2) == (2, 16, 16)      # the tiny models
    assert ssm_op.state_shape(6, 64, 128, 2) == (6, 128, 64)   # 3 heads a group: alone
    f32 = jnp.float32
    assert ssm_op.kernel_ok(jax.ShapeDtypeStruct((64, 32, 128, 128), f32), 8)
    assert not ssm_op.kernel_ok(jax.ShapeDtypeStruct((3, 2, 16, 16), f32), 2)
    assert not ssm_op.kernel_ok(jax.ShapeDtypeStruct((64, 32, 128, 128), jnp.bfloat16), 8)


@pytest.mark.parametrize("shape", [SMALL, PUBLISHED], ids=["small", "published"])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["fallback", "interpret"])
def test_ssm_update_matches_the_token_recurrence(use_kernel, shape):
    """One token a slot, against S <- exp(dt A) S + dt x (x) B written out a
    head; the idle row (valid False) keeps its state to the bit."""
    (B, H, C), state, conv, k, kw = _ssm_case(**shape)
    P, N, G = shape["P"], shape["N"], shape["G"]
    R = state.shape[1]
    xbc, dt = jax.random.normal(k[2], (B, C)), jax.random.normal(k[3], (B, H))
    valid = jnp.array([True, False, True])
    y, s_new, c_new = ssm_op.ssm_update(state, conv, xbc, dt, valid=valid,
                                        use_kernel=use_kernel, interpret=True, **kw)
    win = jnp.concatenate([conv, xbc], 1).reshape(B, 4, C)
    act = jax.nn.silu(jnp.einsum("bkc,kc->bc", win, kw["conv_weight"]) + kw["conv_bias"])
    x = act[:, :H * P].reshape(B, H, P)
    bm = jnp.repeat(act[:, H * P:H * P + G * N].reshape(B, G, N), H // G, 1)
    cm = jnp.repeat(act[:, H * P + G * N:].reshape(B, G, N), H // G, 1)
    dtv = jax.nn.softplus(dt + kw["dt_bias"]) * valid[:, None]
    S = ssm_op.rows_to_heads(state, H) \
        * jnp.exp(-dtv * jnp.exp(kw["a_log"]))[..., None, None] \
        + (dtv[..., None] * x)[..., None] * bm[:, :, None, :]
    want = jnp.sum(S * cm[:, :, None, :], -1) + x
    np.testing.assert_allclose(y.reshape(B, H, P), want, atol=2e-5)
    np.testing.assert_allclose(s_new, _rows(S, R), atol=2e-5)
    np.testing.assert_array_equal(ssm_op.heads_to_rows(ssm_op.rows_to_heads(state, H), R),
                                  state)
    assert jnp.array_equal(s_new[1], state[1]) and jnp.array_equal(c_new[1], conv[1])
    np.testing.assert_array_equal(c_new[0], win[0, 1:].reshape(-1))


def test_ssd_chunk_scan_with_an_initial_state_and_a_padded_tail_matches_token_steps():
    (B, H, C), state, conv, k, kw = _ssm_case(**SMALL)
    T = 24
    xbc, dt = jax.random.normal(k[7], (B, T, C)), jax.random.normal(k[8], (B, T, H))
    n_valid = jnp.array([24, 0, 13])
    y, s_new, c_new = ssm_op.ssm_chunk(state, conv, xbc, dt, n_valid=n_valid,
                                       chunk_size=8, **kw)
    s, c, ys = state, conv, []
    for t in range(T):
        yt, s, c = ssm_op.ssm_update(s, c, xbc[:, t], dt[:, t], valid=t < n_valid,
                                     use_kernel=False, **kw)
        ys.append(yt)
    real = (np.arange(T)[None, :] < np.asarray(n_valid)[:, None])[..., None]
    np.testing.assert_allclose(np.asarray(y) * real, np.asarray(jnp.stack(ys, 1)) * real,
                               atol=5e-5)
    np.testing.assert_allclose(s_new, s, atol=5e-5)
    np.testing.assert_array_equal(c_new, c)
    assert jnp.array_equal(s_new[1], state[1])


def test_the_state_a_chunk_hands_over_is_the_state_the_kernel_reads():
    """A chunk through the scan, then three tokens through the KERNEL, at the
    published slot shape: the same as every token through the recurrence
    written out a head (so the layout `ssm_chunk` writes is the kernel's)."""
    (B, H, C), state, conv, k, kw = _ssm_case(**{**PUBLISHED, "B": 2})
    P, N, G, T = PUBLISHED["P"], PUBLISHED["N"], PUBLISHED["G"], 16
    xbc = jax.random.normal(k[7], (B, T + 3, C))
    dt = jax.random.normal(k[8], (B, T + 3, H))
    n_valid = jnp.array([T, 11])
    _, s, c = ssm_op.ssm_chunk(state, conv, xbc[:, :T], dt[:, :T], n_valid=n_valid,
                               chunk_size=8, **kw)
    ys = []
    for t in range(T, T + 3):
        yt, s, c = ssm_op.ssm_update(s, c, xbc[:, t], dt[:, t], valid=jnp.array([True] * B),
                                     use_kernel=True, interpret=True, **kw)
        ys.append(yt.reshape(B, H, P))
    # the recurrence a head, over each row's own real tokens then the three
    S = ssm_op.rows_to_heads(state, H)
    a = -jnp.exp(kw["a_log"])
    want = []
    for b in range(B):
        Sb, win = S[b], conv[b].reshape(3, C)
        for t in [*range(int(n_valid[b])), *range(T, T + 3)]:
            win = jnp.concatenate([win, xbc[b, t][None]], 0)
            act = jax.nn.silu(jnp.einsum("kc,kc->c", win, kw["conv_weight"])
                              + kw["conv_bias"])
            win = win[1:]
            x = act[:H * P].reshape(H, P)
            bm = jnp.repeat(act[H * P:H * P + G * N].reshape(G, N), H // G, 0)
            cm = jnp.repeat(act[H * P + G * N:].reshape(G, N), H // G, 0)
            dtv = jax.nn.softplus(dt[b, t] + kw["dt_bias"])
            Sb = Sb * jnp.exp(dtv * a)[:, None, None] \
                + (dtv[:, None] * x)[..., None] * bm[:, None, :]
            if t >= T:
                want.append(jnp.sum(Sb * cm[:, None, :], -1) + x)
        np.testing.assert_allclose(s[b], _rows(Sb[None], s.shape[1])[0], atol=1e-4)
    want = jnp.stack(want).reshape(B, 3, H, P)
    np.testing.assert_allclose(jnp.stack(ys, 1), want, atol=1e-4)


@pytest.mark.parametrize("T,K,E,held,lo", [(8, 2, 8, 4, 0), (8, 2, 8, 4, 4),
                                           (40, 3, 16, 8, 8), (200, 6, 16, 8, 0)])
def test_moe_experts_kernel_and_fallback_match_a_loop_over_pairs(T, K, E, held, lo):
    H, F = 128, 48
    k = jax.random.split(jax.random.PRNGKey(T), 4)
    x = jax.random.normal(k[0], (T, H))
    w1 = jax.random.normal(k[1], (held, F, H)) * 0.1
    w2 = jax.random.normal(k[2], (held, F, H)) * 0.1
    wt, ex = jax.lax.top_k(jax.random.uniform(k[3], (T, E)), K)
    real = jnp.arange(T) < T - 2
    want = np.zeros((T, H), np.float32)
    cnt = np.zeros(held + 1, np.int64)
    for t in range(T):
        for j in range(K):
            e = int(ex[t, j]) - lo
            if 0 <= e < held:
                h = np.maximum(np.asarray(w1[e]) @ np.asarray(x[t]), 0) ** 2
                want[t] += float(wt[t, j]) * (h @ np.asarray(w2[e]))
                cnt[e] += bool(real[t])
    cnt[held] = (cnt[:held] > 0).sum()
    for use_kernel in (False, True):
        out, counts = moe_op.moe_experts(x, w1, w2, ex, wt, lo, real=real,
                                         use_kernel=use_kernel, interpret=True)
        np.testing.assert_allclose(out, want, atol=2e-4)
        np.testing.assert_array_equal(counts, cnt)


# (e) ------------------------------------------------------------------------
@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), None),
    (dict(host_cache_pages=4), "host_cache_pages"),
    (dict(spec_k=2), "spec_k"),
    (dict(kv_layout="dense"), "kv_layout='dense' was removed"),
], ids=["prefix_cache", "host_cache_pages", "spec_k", "dense_layout"])
def test_what_recurrent_state_cannot_have_is_refused(kw, word):
    """`prefix_cache=True` was refused until state could be checkpointed at a
    prefix's end (PR 31): it now serves, a second prompt with the first's
    pages resuming from the first's checkpoint (tests/test_minicpm_sala.py
    holds its tokens to a cold engine's)."""
    model = serve_hybrid.build_model(tiny_cfg())
    args = {**ENGINE, "kv_layout": "paged", **kw}
    if word is None:
        eng = LLMEngine(model, **args)
        eng.generate(prompt(32, 1), max_new_tokens=2)
        eng.generate(np.concatenate([prompt(32, 1), prompt(5, 2)]), max_new_tokens=2)
        st = eng.stats()
        assert st["prefix_cache"]["hit_tokens"] == 32
        assert st["recurrent_state"]["checkpoints"]["restored"] == 1
        return
    with pytest.raises(ValueError, match=word):
        LLMEngine(model, **args)


def test_the_prefix_cache_is_off_by_default_for_recurrent_state():
    eng = engine()
    assert eng._prefix is None and eng.stats()["prefix_cache"] is None


def test_parameters_are_created_in_the_configured_dtype():
    cfg = dict(tiny_cfg(), torch_dtype="bfloat16")
    model = serve_hybrid.build_model(cfg)
    kinds = {n.split(".")[-1]: str(p._value.dtype) for n, p in model.named_parameters()}
    f32 = {"dt_bias", "A_log", "D", "e_score_correction_bias"}
    assert {k for k, v in kinds.items() if v == "float32"} == f32
    assert {v for k, v in kinds.items() if k not in f32} == {"bfloat16"}
