"""MiniCPM-SALA (block-sparse + lightning attention) at a small size on the
CPU: the model and the serving engine against the plain reference, the
selection against the published rule worked in numpy, the selected read
against a masked dense pass, the lightning passes against the token
recurrence, compressed keys in shared pages, and the state checkpoints that
let a model with recurrent state share prefixes."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import serve_hybrid, serve_sala  # noqa: E402
from benchmark.reference import minicpm_sala_ref as ref  # noqa: E402
from paddle_tpu.inference import LLMEngine  # noqa: E402
from paddle_tpu.inference.prefix_cache import PrefixCache  # noqa: E402
from paddle_tpu.ops import lightning_attention as la  # noqa: E402
from paddle_tpu.ops import sparse_attention as sa  # noqa: E402
from paddle_tpu.ops.decode_attention import gather_pages  # noqa: E402

SEED = 7
#: scaled down so that contexts lie on both sides of dense_len (24) and hold
#: more blocks (of 4 tokens) than topk (4)
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=4, topk=4,
              init_blocks=1, window_size=6, dense_len=24)


def tiny_cfg(**kw):
    """Config-file keys at a toy size."""
    return {
        "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
        "num_hidden_layers": 4,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
        "rope_theta": 10000, "rms_norm_eps": 1e-6, "scale_emb": 12,
        "scale_depth": 1.4, "dim_model_base": 256, "sparse_config": dict(SPARSE),
        "published": {"num_hidden_layers": 8}, "torch_dtype": "float32", **kw}


ENGINE = dict(max_batch_slots=3, max_seq_len=128, page_size=8, num_pages=49,
              prefill_chunk=8)


def engine(cfg=None, **kw):
    model = serve_sala.build_model(cfg or tiny_cfg(), SEED)
    return LLMEngine(model, **{**ENGINE, **kw})


def prompt(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, 256, n, dtype=np.int32)


# (a) the model and the engine against the reference -----------------------------
def test_model_forward_in_one_pass_matches_the_reference():
    """float32 both sides, products at "highest": what is left is the order
    of float32 sums (the chunked scan against the token recurrence, a soft-max
    in blocks): 1e-5 of logits of size ~2."""
    cfg = tiny_cfg()
    ids = np.stack([prompt(48, 1), prompt(48, 2)])
    model = serve_sala.build_model(cfg, SEED)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.forward(jnp.asarray(ids))._value)
        want = np.asarray(ref.full_logits(cfg, SEED, ids))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("n", [10, 21, 37, 70], ids=lambda n: f"prompt{n}")
def test_engine_prefill_in_chunks_then_decode_matches_the_reference(n):
    """Prompts of 10 and 21 tokens decode inside dense_len (24) and across it;
    37 and 70 are past it from the first token (10 and 18 blocks against topk
    4).  Chunked prefill (8 tokens a chunk), the compressed-key pool, the
    selection inside the decode program and the state carried from chunk to
    chunk must give the tokens the reference puts first: in float32 the served
    token's logit lies within 1e-4 of the reference's best (ties apart, it IS
    the best)."""
    cfg = tiny_cfg()
    eng = engine(cfg)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(eng.generate(prompt(n, n), max_new_tokens=12), np.int32)
        gaps, _ = ref.served_gap(cfg, SEED, [(prompt(n, n), out)], 128)
    assert len(gaps) == 12 and float(np.max(gaps)) < 1e-4
    st = eng.stats()["sparse_attention"]
    assert st["layers"] == 2
    assert st["prefill"]["layer_calls"] == 2 * n and st["decode"]["layer_calls"] == 2 * 11
    spec = sa.SparseSpec(**SPARSE)
    # what the rule gives for the contexts served, query by query
    assert st["decode"]["selected_blocks"] == 2 * sum(
        spec.selected(c) for c in range(n + 1, n + 12))
    assert st["prefill"]["selected_blocks"] == 2 * sum(
        spec.selected(c) for c in range(1, n + 1))
    assert st["decode"]["context_blocks"] == 2 * sum(
        -(-c // 4) for c in range(n + 1, n + 12))


# (b) the selection against the published rule ------------------------------------
def rule_in_numpy(q, ck_of, n, spec):
    """§1's rule for ONE query and ONE K/V head.  q [rep, D]; ck_of(j) = C_j.
    Returns the selected block indices, best first."""
    st, ks, blk = spec.kernel_stride, spec.kernel_size, spec.block_size
    js = [j for j in range(10_000) if st * j + ks <= n]
    nb = -(-n // blk)
    if n <= spec.dense_len:
        return list(range(nb))
    C = np.stack([ck_of(j) for j in js])
    logit = q @ C.T / np.sqrt(q.shape[-1])
    p = np.exp(logit - logit.max(-1, keepdims=True))
    s = (p / p.sum(-1, keepdims=True)).sum(0)
    score = np.zeros(nb)
    for m in range(nb):
        over = [s[i] for i, j in enumerate(js) if st * j < (m + 1) * blk and st * j + ks > m * blk]
        score[m] = max(over, default=0.0)
        if m < spec.init_blocks or (m + 1) * blk > n - spec.window_size:
            score[m] = np.inf
    order = sorted(range(nb), key=lambda m: (-score[m], m))
    return order[:spec.topk]


@pytest.mark.parametrize("n", [3, 24, 25, 41, 64], ids=lambda n: f"context{n}")
def test_select_blocks_is_the_published_rule(n):
    spec = sa.SparseSpec(**SPARSE)
    rng = np.random.default_rng(n)
    H, rep, D, L = 2, 2, 16, 64
    k = rng.standard_normal((L, H, D)).astype(np.float32)
    q = rng.standard_normal((H, rep, D)).astype(np.float32)
    # the gathered pool's row i holds C_{i - shift}
    rows = np.zeros((H, L // 2, D), np.float32)
    for j in range((L - 4) // 2 + 1):
        rows[:, j + spec.shift] = k[2 * j:2 * j + 4].mean(0)
    idx, cnt, picked = sa.select_blocks(jnp.asarray(q)[None, None], jnp.asarray(rows)[None],
                                        jnp.asarray([[n]], jnp.int32), spec)
    assert int(cnt[0, 0]) == spec.selected(n) == int(picked[0, 0])
    for h in range(H):
        want = rule_in_numpy(q[h], lambda j: k[2 * j:2 * j + 4, h].mean(0), n, spec)
        got = [int(x) for x in idx[0, 0, h, :int(cnt[0, 0])]]
        assert sorted(got) == sorted(want)
        if n > spec.dense_len:
            assert got == want  # forced blocks first, then by score


def test_the_count_of_picked_blocks_is_read_from_the_list_not_from_the_length():
    """`picked` is what `stats()["sparse_attention"]` counts and the
    benchmark holds against the rule's arithmetic.  A sound order lists `cnt`
    distinct blocks of the context; scores that cannot be ordered (NaN: every
    unforced block gets rank 0, their indices add up in entry 0) leave a list
    that holds fewer, and the count says so where `cnt` cannot."""
    spec = sa.SparseSpec(**SPARSE)
    rng = np.random.default_rng(5)
    H, rep, D, L, n = 2, 2, 16, 64, 64
    q = rng.standard_normal((1, 1, H, rep, D)).astype(np.float32)
    rows = rng.standard_normal((1, H, L // 2, D)).astype(np.float32)
    ctx = jnp.asarray([[n]], jnp.int32)
    _, cnt, picked = sa.select_blocks(jnp.asarray(q), jnp.asarray(rows), ctx, spec)
    assert int(picked[0, 0]) == int(cnt[0, 0]) == spec.topk
    _, cnt, picked = sa.select_blocks(jnp.asarray(q * np.nan), jnp.asarray(rows), ctx, spec)
    assert int(cnt[0, 0]) == spec.topk and int(picked[0, 0]) < spec.topk


# (c) the selected read against a masked dense pass -------------------------------
@pytest.mark.parametrize("use_kernel", [False, True], ids=["fallback", "interpret"])
def test_the_selected_read_matches_a_masked_dense_pass(use_kernel):
    """Published head sizes (16 query heads a K/V head, 128 wide), pages of
    two blocks, rows at a long context, at one still read whole, at context 1
    and idle.  float32 pools: the kernel's online soft-max against one pass
    differs by the order of the sums, 1e-5."""
    rng = np.random.default_rng(0)
    B, Hq, H, D, ps, P, M = 4, 32, 2, 128, 32, 60, 14
    spec = sa.SparseSpec(kernel_size=8, kernel_stride=4, block_size=16, topk=6,
                         init_blocks=1, window_size=40, dense_len=96)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal((P, H, ps, D)), jnp.float32)
                      for _ in range(2))
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[:B * M].reshape(B, M), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    n = np.array([430, 77, 1, 200])
    K = min(spec.list_len, M * ps // 16)
    idx = np.zeros((B, H, K), np.int32)
    cnt = np.array([spec.selected(int(c)) for c in n])
    cnt[3] = 0  # an idle row: reads nothing, gives zeros
    for b in range(B):
        blocks = -(-int(n[b]) // 16)
        for h in range(H):
            sel = rng.permutation(blocks)[:cnt[b]]
            if cnt[b] and blocks - 1 not in sel:
                sel[0] = blocks - 1  # the query's own block is always read
            idx[b, h, :cnt[b]] = sel
    out = np.asarray(sa.sparse_paged_attention(
        q, k_pool, v_pool, tbl, jnp.asarray(idx), jnp.asarray(cnt, jnp.int32),
        jnp.asarray(n, jnp.int32), spec, use_kernel=use_kernel, interpret=True))
    kk, vv = np.asarray(gather_pages(k_pool, tbl)), np.asarray(gather_pages(v_pool, tbl))
    for b in range(B):
        for h in range(H):
            mask = np.zeros(M * ps, bool)
            for m in idx[b, h, :cnt[b]]:
                mask[m * 16:(m + 1) * 16] = True
            mask &= np.arange(M * ps) < n[b]
            qh = np.asarray(q[b]).reshape(H, Hq // H, D)[h]
            if not mask.any():
                want = np.zeros_like(qh)
            else:
                s = np.where(mask, qh @ kk[b, h].T / np.sqrt(D), -np.inf)
                p = np.exp(s - s.max(-1, keepdims=True))
                want = (p / p.sum(-1, keepdims=True)) @ vv[b, h]
            np.testing.assert_allclose(out[b].reshape(H, Hq // H, D)[h], want, atol=1e-5)


def test_a_page_shared_by_two_continuations_holds_the_same_compressed_keys():
    """A compressed key lives in the page of its LAST token, so the pages of a
    common prefix hold the same compressed keys whatever follows them (what
    lets the prefix cache share the page)."""
    common = prompt(16, 3)
    eng = engine(max_batch_slots=2)
    for salt in (4, 5):
        eng.submit(np.concatenate([common, prompt(9, salt)]), max_new_tokens=12)
    for _ in range(8):      # 4 chunks each, one a tick: both are decoding now
        eng.step()
    pages = [list(p) for p in eng._slot_pages[:2]]
    assert all(len(p) >= 4 for p in pages)
    pools = [np.asarray(c[2]) for c in eng.caches if len(c) == 3]
    assert pages and pages[0][:2] != pages[1][:2]   # private copies of the prefix
    for ck in pools:
        for i in range(2):                      # the two pages of the common prefix
            a, b = ck[pages[0][i]], ck[pages[1][i]]
            rows = slice(1, None) if i == 0 else slice(None)  # row 0 of page 0: no C_{-1}
            np.testing.assert_array_equal(a[:, rows], b[:, rows])
            assert np.abs(a[:, rows]).max() > 0
        # the first key that ends in the continuation differs
        assert not np.array_equal(ck[pages[0][2]][:, 0], ck[pages[1][2]][:, 0])
    eng.run_until_complete()


# (d) lightning attention against the token recurrence ----------------------------
def recurrence(state, q, k, v, lam):
    """S <- lam S + k^T v, o = q S, token by token.  state [H, N, P]."""
    outs = []
    for t in range(q.shape[0]):
        state = lam[:, None, None] * state + k[t][:, :, None] * v[t][:, None, :]
        outs.append(np.einsum("hn,hnp->hp", q[t], state))
    return np.stack(outs), state


SMALL, PUBLISHED = (3, 4, 16, 16), (2, 32, 128, 128)


@pytest.mark.parametrize("use_kernel,shape", [
    (False, SMALL), (False, PUBLISHED), (True, PUBLISHED),  # the kernel's tiling wants 128 lanes
], ids=["fallback-small", "fallback-published", "interpret-published"])
def test_lightning_update_matches_the_recurrence_and_leaves_idle_rows_alone(
        use_kernel, shape):
    B, H, N, P = shape
    rng = np.random.default_rng(1)
    state = rng.standard_normal((B, H, N, P)).astype(np.float32)
    q, k = (rng.standard_normal((B, H, N)).astype(np.float32) for _ in range(2))
    v = rng.standard_normal((B, H, P)).astype(np.float32)
    lam = la.log_decay(H)
    valid = np.arange(B) != B - 1                 # the last row is idle
    o, new = la.lightning_update(jnp.asarray(state), jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), lam, jnp.asarray(valid),
                                 use_kernel=use_kernel, interpret=True)
    for b in range(B - 1):
        want_o, want_s = recurrence(state[b], q[b][None], k[b][None], v[b][None],
                                    np.exp(np.asarray(lam)))
        np.testing.assert_allclose(np.asarray(o[b]), want_o[0], rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(new[b]), want_s, rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[B - 1]), state[B - 1])  # to the bit


def test_the_decay_slopes_are_the_assumed_ones():
    lam = np.exp(np.asarray(la.log_decay(32)))
    assert lam[0] == pytest.approx(np.exp(-2.0 ** -0.25)) and lam[31] == pytest.approx(np.exp(-2.0 ** -8))
    assert np.all(np.diff(lam) > 0)


def test_lightning_chunk_from_a_state_with_a_padded_tail_matches_token_steps():
    """A chunk of 16 with 11 real tokens from a non-zero state, in scan steps
    of 8: the outputs of the real tokens and the state after the 11th are the
    recurrence's; a row with no real token keeps its state to the bit.  The
    chunked form sums in another order: 1e-4 of values of size ~10."""
    rng = np.random.default_rng(2)
    B, T, H, D = 2, 16, 4, 16
    state = rng.standard_normal((B, H, D, D)).astype(np.float32)
    q, k, v = (rng.standard_normal((B, T, H, D)).astype(np.float32) for _ in range(3))
    lam = la.log_decay(H)
    with jax.default_matmul_precision("highest"):
        o, new = la.lightning_chunk(jnp.asarray(state), jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), lam, jnp.asarray([11, 0]), 8)
    want_o, want_s = recurrence(state[0], q[0, :11], k[0, :11], v[0, :11],
                                np.exp(np.asarray(lam)))
    np.testing.assert_allclose(np.asarray(o[0, :11]), want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(new[0]), want_s, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(new[1]), state[1])


# (e) state checkpoints: prefixes shared by a model with recurrent state ----------
def nemotron_engine(**kw):
    from test_nemotron_h import ENGINE as NE, tiny_cfg as ncfg
    model = serve_hybrid.build_model(ncfg(), SEED)
    return LLMEngine(model, **{**NE, "page_size": 8, "num_pages": 49,
                               "prefill_chunk": 8, **kw})


@pytest.mark.parametrize("make", [engine, nemotron_engine], ids=["minicpm_sala", "nemotron_h"])
def test_greedy_tokens_with_the_prefix_cache_equal_those_without(make):
    """A document of 6 pages, then three questions of it (one ending on a page
    boundary, which leaves a second checkpoint): every question is a hit that
    resumes from the document's checkpoint, and reads what a cold engine reads."""
    doc = prompt(48, 6)
    asks = [doc] + [np.concatenate([doc, prompt(n, n)]) for n in (5, 11, 16)]
    outs = {}
    for on in (False, True):
        eng = make(prefix_cache=on, **({"state_checkpoints": 2} if on else {}))
        outs[on] = [eng.generate(p, max_new_tokens=8) for p in asks]
        st = eng.stats()
        if on:
            ck = st["recurrent_state"]["checkpoints"]
            assert ck["stored"] == 2 and ck["restored"] == 3 and ck["held"] == 2
            assert ck["bytes"] == 2 * st["recurrent_state"]["bytes"] // 3  # 2 entries, 3 slots
            assert st["prefix_cache"]["hit_tokens"] == 3 * 48
            assert st["prefix_cache"]["cow_copies"] == 0   # whole pages only
        else:
            assert st["prefix_cache"] is None and st["recurrent_state"]["checkpoints"] is None
    assert outs[True] == outs[False]


def test_a_hit_deeper_than_the_deepest_checkpoint_is_cut_back_to_it():
    """One entry in the pool.  A 48-token prompt indexes pages 0..5 and leaves
    the checkpoint after page 5; its first 32 tokens, sent next, find pages
    but no checkpoint at their end, are computed whole and take the entry over
    (after page 3).  A prompt sharing all 6 pages then resumes at 32, not at
    48, and reads a cold engine's tokens."""
    base = prompt(52, 7)
    eng = engine(prefix_cache=True, state_checkpoints=1)
    eng.generate(base[:48], max_new_tokens=2)
    eng.generate(base[:32], max_new_tokens=2)
    st = eng.stats()
    assert st["prefix_cache"]["hit_tokens"] == 0
    ck = st["recurrent_state"]["checkpoints"]
    assert (ck["stored"], ck["evicted"], ck["held"]) == (2, 1, 1)
    ask = np.concatenate([base[:50], prompt(7, 8)])
    got = eng.generate(ask, max_new_tokens=6)
    assert eng.stats()["prefix_cache"]["hit_tokens"] == 32
    assert got == engine().generate(ask, max_new_tokens=6)


def test_a_question_that_ends_inside_a_page_leaves_no_page_in_the_index():
    """A checkpoint is stored only where a prompt ends on a page boundary, so
    the full pages of any other prompt could never be resumed at: they are
    not indexed, and every page the question took is free again when it
    finishes (the document's 4 stay, held by the index alone)."""
    doc = prompt(32, 50)
    eng = engine(prefix_cache=True)
    eng.generate(doc, max_new_tokens=2)
    nodes, free = len(eng._prefix), len(eng._free_pages)
    for n in (3, 9, 21):                          # 0, 1 and 2 full pages of its own
        eng.generate(np.concatenate([doc, prompt(n, 51 + n)]), max_new_tokens=4)
        assert (len(eng._prefix), len(eng._free_pages)) == (nodes, free)
    assert eng.stats()["recurrent_state"]["checkpoints"]["restored"] == 3


def test_eviction_frees_the_checkpoint_and_a_full_pool_gives_up_its_oldest():
    eng = engine(prefix_cache=True, state_checkpoints=2, num_pages=13)
    docs = [prompt(16, 20 + i) for i in range(4)]
    for d in docs[:3]:                              # the third takes the first's entry
        eng.generate(d, max_new_tokens=2)
    ck = eng.stats()["recurrent_state"]["checkpoints"]
    assert (ck["stored"], ck["evicted"], ck["held"], ck["capacity"]) == (3, 1, 2, 2)
    # 12 pages: a 64-token prompt needs 9, so cached documents must go, entries with them
    eng.generate(prompt(64, 30), max_new_tokens=2)
    ck = eng.stats()["recurrent_state"]["checkpoints"]
    assert ck["evicted"] >= 2 and ck["held"] == ck["stored"] - ck["evicted"]
    assert len(eng._ckpt_free) == ck["capacity"] - ck["held"]
    assert not eng._prefix.released


@pytest.mark.parametrize("kw,word", [
    (dict(host_cache_pages=4), "host_cache_pages"),
    (dict(spec_k=2), "spec_k"),
    (dict(state_checkpoints=2), "state_checkpoints"),
    (dict(cache_dtype="int8"), "int8"),
], ids=["host_cache_pages", "spec_k", "checkpoints_without_the_cache", "int8_pages"])
def test_what_this_model_cannot_have_is_refused(kw, word):
    model = serve_sala.build_model(tiny_cfg())
    with pytest.raises(ValueError, match=word):
        LLMEngine(model, **{**ENGINE, **kw})


def test_the_prefix_cache_is_on_request_only_and_counts_in_the_registry():
    from paddle_tpu.observability import metrics

    assert engine()._prefix is None
    eng = engine(prefix_cache=True)
    assert eng._prefix.stateful and eng.stats()["recurrent_state"]["checkpoints"]["capacity"] == 8
    fam = metrics.REGISTRY.get("llm_state_checkpoints_total")
    before = {k[0]: c.value for k, c in fam.series()}
    eng.generate(prompt(16, 40), max_new_tokens=2)
    eng.generate(np.concatenate([prompt(16, 40), prompt(3, 41)]), max_new_tokens=2)
    after = {k[0]: c.value for k, c in fam.series()}
    assert after["stored"] - before.get("stored", 0) == 1
    assert after["restored"] - before.get("restored", 0) == 1
    sel = metrics.REGISTRY.get("llm_sparse_blocks_selected_total")
    assert sum(c.value for _, c in sel.series()) > 0


def test_the_models_scopes_are_in_the_compiled_programs():
    """The selection, a chunk's masked pass and the chunked scan are plain
    XLA: their fusions are named `fusion.N`, and only the instructions'
    `op_name` says what they belong to (PERF.md §7: what a reduction by scope
    needs).  Both engine programs carry the selection's scope, the gather of
    the compressed keys sits under it (only the selection reads the gathered
    keys: 2/3 of its bytes), the chunk's scopes are in the chunk program
    alone, the state kernel's in the decode program."""
    import paddle_tpu.framework.random as fr

    eng = engine()
    B, C = eng.n_slots, eng.prefill_chunk
    i32 = np.int32
    chunk = eng._get_chunk_prefill().lower(
        eng._params, eng._buffers, eng.caches, np.zeros((1, eng.M), i32),
        np.zeros((1, C), i32), np.zeros((1,), i32), i32(3), *eng._lora_args([0]),
        *eng._chunk_extra(0)).compile().as_text()
    decode = eng._get_decode(1).lower(
        *eng._cache_args(), np.zeros((B, 1), i32), eng._feed,
        np.ones((B,), bool), np.zeros((B,), i32), *eng._sampling_knobs(), eng._mask_all_true, fr.default_generator().key,
        np.uint32(0), *eng._lora_args([0] * B), *eng._accs()).compile().as_text()
    for hlo in (chunk, decode):
        assert "/sparse_select/" in hlo
        gathers = [ln for ln in hlo.splitlines() if " gather(" in ln and "sparse_attention" in ln]
        assert gathers and any("/sparse_select/" in ln for ln in gathers)
    for scope in ("sparse_chunk_attention", "lightning_chunk_scan"):
        assert f"/{scope}/" in chunk and f"/{scope}/" not in decode
    assert "/lightning_update/" in decode


# (f) the index alone -----------------------------------------------------------
def test_a_stateful_index_matches_to_a_checkpoint_and_shares_no_partial_page():
    pc = PrefixCache(4, stateful=True)
    p = np.arange(14, dtype=np.int32)
    assert pc.insert(p, [5, 6, 7, 8]) == []               # ends inside a page: no checkpoint can follow
    assert pc.insert(p[:12], [5, 6, 7]) == [5, 6, 7]
    assert pc.match(p) == (0, [])                          # no checkpoint: nothing to resume
    assert pc.checkpoint_node(p[:6]) is None               # ends inside a page
    key = pc.checkpoint_node(p[:8])
    pc.attach_checkpoint(key, 3)
    assert pc.checkpoint_node(p[:8]) is None               # it has one now
    assert pc.match(p) == (8, [5, 6]) and pc.checkpoint_of(6) == 3
    assert pc.match(p[:9]) == (8, [5, 6]) and pc.match(p[:8]) == (0, [])  # one token is always computed
    # the page's node goes: its entry comes back
    assert pc.evict_page(7) is not None and pc.released == []
    assert pc.evict_page(6)[2] == 6 and pc.released == [3] and pc.checkpoint_of(6) is None
    pc.attach_checkpoint(pc.checkpoint_node(p[:4]), 1)
    assert pc.steal_checkpoint() == 1 and pc.steal_checkpoint() is None
    assert pc.match(p) == (0, [])
    plain = PrefixCache(4)
    assert plain.insert(p, [5, 6, 7, 8]) == [5, 6, 7, 8] and plain.match(p) == (13, [5, 6, 7, 8])
