"""Granite-4.0-H (Mamba-2 + NoPE attention, a dense gated MLP in every layer,
muP multipliers, a tied head) at a small size on the CPU: the serving engine
against the plain reference, each multiplier and the tied head against a
model without it, the paged kernel at 64-wide heads (two a 128-lane row)
against the dense path, the carried-chunk counter, and what the engine and
the config refuse."""
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

from benchmark import serve_granite  # noqa: E402
from benchmark import weights_granite_hybrid as W  # noqa: E402
from benchmark.reference import granite_hybrid_ref as ref  # noqa: E402
from paddle_tpu.inference import LLMEngine  # noqa: E402
from paddle_tpu.inference import llm_server  # noqa: E402
from paddle_tpu.models.granite_hybrid import GraniteHybridConfig  # noqa: E402
from paddle_tpu.models.kv_cache import paged_attention_update  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.ops import decode_attention as da  # noqa: E402
from paddle_tpu.tensor.tensor import Tensor  # noqa: E402

SEED = 11
ENGINE = dict(max_batch_slots=3, max_seq_len=128, page_size=16, num_pages=25,
              prefill_chunk=16)


def tiny_cfg(**kw):
    """Config-file keys at a toy size whose layers, not the embedding, lead
    the residual stream (narrower ones leave the tied head echoing its input
    token, and a token comparison then sees nothing)."""
    with open(os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, vocab_size=256, num_hidden_layers=8,
               layer_types=["mamba", "attention"] + ["mamba"] * 6,
               num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=16,
               mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
               shared_intermediate_size=1024, torch_dtype="float32")
    cfg.update(kw)
    return cfg


def engine(cfg=None, **kw):
    model = serve_granite.build_model(cfg or tiny_cfg(), SEED)
    return LLMEngine(model, kv_layout="paged", **{**ENGINE, **kw})


def prompt(n, salt=0):
    return np.random.default_rng([SEED, salt]).integers(0, 256, n, dtype=np.int32)


def ref_logits(cfg, toks, pad_to=64):
    toks = np.atleast_2d(toks)
    padded = np.pad(toks, ((0, 0), (0, pad_to - toks.shape[1])))
    return np.asarray(ref.full_logits(cfg, SEED, padded))[:, :toks.shape[1]]


# ------------------------------------------------------ model vs reference
def test_model_forward_without_cache_matches_the_reference():
    cfg = tiny_cfg()
    model = serve_granite.build_model(cfg, SEED)
    toks = np.stack([prompt(24, 1), prompt(24, 2)])
    got = np.asarray(model(jnp.asarray(toks))._value)
    want = ref_logits(cfg, toks)
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    # the argmax is not the input token: the comparison sees the layers
    assert (want.argmax(-1) == toks).mean() < 0.5


@pytest.mark.parametrize("n", [12, 40], ids=["one_chunk", "three_chunks"])
def test_engine_prefill_then_decode_matches_the_reference(monkeypatch, n):
    """12 prompt tokens = one chunk of 16; 40 = chunks of 16, 16 and 8 with
    the state carried between them.  The first token's logits equal the
    reference's full forward pass, and every served token is the
    reference's own best (float32: a gap is rounding)."""
    cfg = tiny_cfg()
    rows = []
    orig = llm_server.LLMEngine._host_select
    monkeypatch.setattr(llm_server.LLMEngine, "_host_select",
                        lambda self, row, req: rows.append(np.array(row)) or orig(self, row, req))
    eng = engine(cfg)
    p = prompt(n, 3)
    out = np.asarray(eng.generate(p, max_new_tokens=12))
    np.testing.assert_allclose(rows[0], ref_logits(cfg, p)[0, -1], atol=2e-6, rtol=0)
    gaps, _ = ref.served_gap(cfg, SEED, [(p, out)], 64)
    assert gaps.max() < 1e-5
    st = eng.stats()
    assert st["recurrent_state"]["carried_chunks"] == -(-n // 16) - 1
    assert st["cache_kinds"]["recurrent"]["layers"] == 7
    assert st["cache_kinds"]["paged_kv"]["layers"] == 1


@pytest.mark.parametrize("key,dropped", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0)])
def test_a_model_without_a_multiplier_fails_the_comparison(key, dropped):
    """The model built with one multiplier at its neutral value (the
    attention's at 1/sqrt(head_dim), the default scale) against the
    reference with all of them: the logits part."""
    cfg = tiny_cfg()
    model = serve_granite.build_model(dict(cfg, **{key: dropped}), SEED)
    toks = prompt(24, 4)[None]
    got = np.asarray(model(jnp.asarray(toks))._value)
    assert np.abs(got - ref_logits(cfg, toks)).max() > 2e-5   # 10 x the pass tolerance


def test_the_head_is_the_embedding():
    """One [vocab, hidden] leaf, and a head drawn apart from it (an untied
    model's) fails the comparison."""
    cfg = tiny_cfg()
    model = serve_granite.build_model(cfg, SEED)
    names = [n for n, _ in model.named_parameters()]
    assert "lm_head" not in names and names.count("embed_tokens") == 1
    assert model.num_params == W.n_params(cfg)
    toks = prompt(24, 5)[None]
    want = ref_logits(cfg, toks)
    other = jax.random.normal(jax.random.PRNGKey(0), (256, 128)) * W.INIT_SCALE * 18
    model._head = lambda hidden: Tensor(jnp.einsum("bth,vh->btv", hidden, other) / 8.0)
    got = np.asarray(model(jnp.asarray(toks))._value)
    assert np.abs(got - want).max() > 1e-4


# ---------------------------------------------- 64-wide heads, two a row
def _packed_case(S, B, offsets, H=8, Hkv=4, D=64, ps=128, M=3, seed=0):
    rows, W_ = da.pool_layout(Hkv, D)
    assert (rows, W_) == (Hkv // 2, 128)
    P = B * M + 1
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k[0], (B, S, H, D), jnp.float32)
    kp = jax.random.normal(k[1], (P, rows, ps, W_), jnp.float32)
    vp = jax.random.normal(k[2], (P, rows, ps, W_), jnp.float32)
    tbl = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    return q, kp, vp, jnp.asarray(offsets, jnp.int32), tbl


def _unpacked(pool, f=2):
    """[P, rows, ps, f*D] -> [P, rows*f, ps, D]: kv head f*j + e is lanes
    [e*D, (e+1)*D) of row j."""
    P, R, ps, W_ = pool.shape
    return pool.reshape(P, R, ps, f, W_ // f).transpose(0, 1, 3, 2, 4).reshape(
        P, R * f, ps, W_ // f)


def _dispatches():
    fam = metrics.REGISTRY.get("llm_attn_kernel_total")
    out = {}
    for labels, child in fam.series():
        out[labels[0]] = out.get(labels[0], 0) + child.value
    return out


@pytest.mark.parametrize("S,B,offsets", [(1, 3, [5, 130, 300]), (40, 1, [100])],
                         ids=["decode_rows", "chunk"])
def test_the_paged_kernel_at_64_wide_heads_matches_the_dense_path(S, B, offsets):
    q, kp, vp, off, tbl = _packed_case(S, B, offsets)
    before = _dispatches()
    got = da.paged_decode_attention(q, kp, vp, off, tbl, scale=0.015625)
    after = _dispatches()
    assert after.get("paged_kernel", 0) == before.get("paged_kernel", 0) + 1
    assert after.get("paged_dense", 0) == before.get("paged_dense", 0)
    want = da._paged_dense(q, _unpacked(kp), _unpacked(vp), off, tbl, None, None,
                           0.015625)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_scale_reaches_the_kernel_through_the_cache_update():
    """The new keys go two heads a row; the kernel's output at a scale given
    equals the dense path's over the unpacked pools, and the default is
    1/sqrt(64), the head's own width, not the row's."""
    q, kp, vp, off, tbl = _packed_case(1, 3, [5, 130, 300], seed=1)
    kv = jax.random.normal(jax.random.PRNGKey(2), (3, 1, 4, 64))

    def attend(scale):
        cache = (Tensor(kp), Tensor(vp), off, Tensor(tbl))
        new, out = paged_attention_update(cache, Tensor(q), Tensor(kv), Tensor(kv),
                                          off, scale=scale)
        return new[0]._value, new[1]._value, np.asarray(out._value)

    k_new, v_new, got = attend(0.015625)
    want = da._paged_dense(q, _unpacked(k_new), _unpacked(v_new), off, tbl, None,
                           None, 0.015625)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    default, eighth = attend(None)[2], attend(0.125)[2]
    np.testing.assert_allclose(default, eighth, atol=1e-6)
    assert np.abs(got - default).max() > 1e-3


def test_the_engine_rides_the_kernel_at_64_wide_heads():
    """hidden 256 over 4 heads: 64-wide heads, two a 128-lane row of 128-token
    pages; every attention call of the engine's programs takes the kernel,
    and the tokens are the reference's."""
    cfg = tiny_cfg(hidden_size=256)
    before = _dispatches()
    eng = engine(cfg, page_size=128, num_pages=4, max_seq_len=256, prefill_chunk=32)
    assert eng.caches[1][0].shape == (4, 1, 128, 128)   # 2 kv heads of 64, one row
    p = prompt(40, 6)
    out = np.asarray(eng.generate(p, max_new_tokens=6))
    after = _dispatches()
    assert after.get("paged_dense", 0) == before.get("paged_dense", 0)
    assert after.get("paged_kernel", 0) > before.get("paged_kernel", 0)
    gaps, _ = ref.served_gap(cfg, SEED, [(p, out)], 64)
    assert gaps.max() < 1e-5


# ------------------------------------------------------ the carried chunks
def test_carried_chunks_are_counted_exactly():
    """Chunks of 16: prompts of 10, 16, 17, 40 and 48 tokens take 1, 1, 2, 3
    and 3 chunks, of which 0, 0, 1, 2 and 2 resume their prompt's state; a
    prompt resumed from a checkpoint carries nothing into its first chunk."""
    fam = metrics.REGISTRY.get("llm_state_carried_chunks_total")
    eng = engine()
    v0 = fam.value
    for i, n in enumerate((10, 16, 17, 40, 48)):
        eng.generate(prompt(n, 10 + i), max_new_tokens=2)
    assert eng.stats()["recurrent_state"]["carried_chunks"] == 5
    assert fam.value - v0 == 5
    eng = engine(prefix_cache=True, state_checkpoints=2)
    eng.generate(prompt(32, 20), max_new_tokens=2)          # 2 chunks: 1 carried
    eng.generate(np.concatenate([prompt(32, 20), prompt(20, 21)]), max_new_tokens=2)
    st = eng.stats()
    assert st["recurrent_state"]["checkpoints"]["restored"] == 1
    # the second prompt: 32 tokens from the checkpoint, then 16 + 4: 1 carried
    assert st["recurrent_state"]["carried_chunks"] == 2


def test_a_model_without_recurrent_state_counts_no_carried_chunk():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    m = LlamaForCausalLM(LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=64,
                                     num_hidden_layers=1, num_attention_heads=4,
                                     num_key_value_heads=2, max_position_embeddings=128))
    fam = metrics.REGISTRY.get("llm_state_carried_chunks_total")
    v0 = fam.value
    eng = LLMEngine(m, kv_layout="paged", **ENGINE)
    eng.generate(prompt(40, 1), max_new_tokens=2)
    assert fam.value == v0 and eng.stats()["recurrent_state"] is None


# ------------------------------------------------------------ what is refused
@pytest.mark.parametrize("kw,word", [
    (dict(num_local_experts=8), "num_local_experts"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(mamba_n_groups=3), "mamba_n_groups"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
])
def test_the_config_refuses_what_is_not_built(kw, word):
    with pytest.raises(ValueError, match=word):
        GraniteHybridConfig.tiny(**kw)


@pytest.mark.parametrize("kw,word", [
    (dict(host_cache_pages=4), "host_cache_pages"),
    (dict(spec_k=2), "spec_k"),
    (dict(prefix_cache=True, state_checkpoints=0), "state_checkpoints"),
], ids=["kv_tiers", "speculation", "prefix_cache_without_checkpoints"])
def test_the_engine_refuses_what_recurrent_state_cannot_have(kw, word):
    with pytest.raises(ValueError, match=word):
        engine(**kw)


def test_an_int8_cache_is_refused_for_a_pool_of_two_heads_a_row():
    cfg = tiny_cfg(hidden_size=256)
    with pytest.raises(ValueError, match="int8"):
        eng = engine(cfg, page_size=128, num_pages=4, max_seq_len=256,
                     prefill_chunk=32, cache_dtype="int8")
        eng.generate(prompt(8, 1), max_new_tokens=2)


def test_parameters_are_created_in_the_configured_dtype():
    model = serve_granite.build_model(tiny_cfg(torch_dtype="bfloat16"))
    kinds = {n.split(".")[-1]: str(p._value.dtype) for n, p in model.named_parameters()}
    f32 = {"dt_bias", "A_log", "D"}
    assert {k for k, v in kinds.items() if v == "float32"} == f32
    assert {v for k, v in kinds.items() if k not in f32} == {"bfloat16"}


# ----------------------------------------------- what the others still run
def test_the_engines_programs_keep_their_names():
    from test_nemotron_h import engine as nemotron_engine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    m = LlamaForCausalLM(LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=64,
                                     num_hidden_layers=1, num_attention_heads=4,
                                     num_key_value_heads=2, max_position_embeddings=128))
    names = lambda eng: [j.__name__ for j, _, _ in eng._programs()]  # noqa: E731
    assert names(LLMEngine(m, kv_layout="paged", **ENGINE)) == [
        "llm_mixed", "cow_copy_pages", "llm_decode"]
    assert names(nemotron_engine()) == ["llm_prefill_chunk", "llm_decode"]
    assert names(engine()) == ["llm_prefill_chunk", "llm_decode"]
