"""LlamaAttention's Q/K/V projections: ONE product over the three weights for
a decode step and for every paged call (decode, chunk, mixed, verify), the
training forward and a static cache's prefill keep three.

- the new path against the three-product formula on bfloat16 rows at GQA
  32 : 8, D = 128 (a decode step, a 256-row chunk, a mixed call): equal bit
  for bit;
- int8 pages, the K+1 verify step and LoRA adapters serve the same tokens
  and write the same pages as under the old rule (one product only at S = 1);
- the compiled paged programs of a tiny engine hold one product a layer
  covering the three weights, and no other weight-sized instruction under
  ``attention`` / ``attention/qkv_proj`` than that product's own reads;
- the path is counted once a call site a compiled program, beside the
  attention dispatch.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import census as _census
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.llm_server import _attn_dispatch_series
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama import LlamaAttention, _rope_cache
from paddle_tpu.models.lora import AdapterRegistry, LoraAdapter, lora_sites
from paddle_tpu.tensor.tensor import Tensor

H, KV, D = 32, 8, 128
HIDDEN = H * D


def _formula(h, wq, wk, wv, cos, sin, pos):
    """Three products, the split into heads and rotate-half rope with a
    position a token [B, S], op by op."""
    B, S = h.shape[:2]

    def rope(x):
        c = cos[pos][:, :, None, :]
        s = sin[pos][:, :, None, :]
        x1, x2 = x[..., :D // 2], x[..., D // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    q = jnp.matmul(h, wq).reshape(B, S, H, D)
    k = jnp.matmul(h, wk).reshape(B, S, KV, D)
    v = jnp.matmul(h, wv).reshape(B, S, KV, D)
    return rope(q), rope(k), v


@pytest.fixture(scope="module")
def attn():
    paddle.seed(3)
    cfg = LlamaConfig(hidden_size=HIDDEN, num_attention_heads=H,
                      num_key_value_heads=KV, num_hidden_layers=1,
                      tensor_parallel=False)
    return LlamaAttention(cfg).bfloat16()


@pytest.mark.parametrize("case", ["decode", "chunk", "mixed"])
def test_one_product_and_rope_equal_the_three_product_formula(attn, case):
    rng = np.random.default_rng(5)
    B, S = {"decode": (32, 1), "chunk": (1, 256), "mixed": (1, 288)}[case]
    h = jnp.asarray(rng.standard_normal((B, S, HIDDEN)), jnp.bfloat16)
    cos, sin = (jnp.asarray(t, jnp.bfloat16) for t in _rope_cache(D, 4096, 1e6))
    if case == "decode":  # a slot's own depth a row
        offset = jnp.asarray(rng.integers(0, 4000, B), jnp.int32)
        pos = offset[:, None]
    elif case == "chunk":  # the chunk's offset, as the paged cache holds it
        offset = jnp.asarray([512], jnp.int32)
        pos = 512 + jnp.arange(S)[None, :]
    else:  # 256 chunk rows, then 32 decode rows at their own positions
        pos = jnp.concatenate([512 + jnp.arange(256),
                               jnp.asarray(rng.integers(0, 4000, 32))])[None, :]
        offset = pos.astype(jnp.int32)
    got = attn._qkv_rope(Tensor(h), (Tensor(cos), Tensor(sin)), offset,
                         one_product=True)
    want = _formula(h, attn.q_proj.weight._value, attn.k_proj.weight._value,
                    attn.v_proj.weight._value, cos, sin, pos)
    for name, g, w in zip("qkv", got, want):
        g = np.asarray(g._value)
        assert g.dtype == np.asarray(w).dtype == jnp.bfloat16, name
        assert np.array_equal(g, np.asarray(w)), (case, name)


# ----------------------------------------------------- the old rule, served
V = 512


@pytest.fixture(scope="module")
def model():
    """Tiny depth and vocabulary at 128-wide heads, weights wider than any
    activation or page pool of the engines below.  From hidden 512 the
    CPU's products of one and of three weights agree bit for bit; at 256
    its small-matrix path rounds a few elements apart under either rule."""
    paddle.seed(9)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           hidden_size=512, num_attention_heads=4,
                           num_key_value_heads=2, intermediate_size=256,
                           vocab_size=V, max_position_embeddings=256)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return m


def _old_rule(monkeypatch):
    """One product only at a decode step, three for every other call: the
    rule before paged calls took one."""
    new = LlamaAttention._qkv
    monkeypatch.setattr(
        LlamaAttention, "_qkv",
        lambda self, h, one_product: new(self, h,
                                         one_product and h.shape[1] == 1))


def _qkv_paths():
    return {"/".join(labels): n for labels, n in _attn_dispatch_series()
            if labels[0].startswith("qkv_")}


def _serve(model, **kw):
    """Tokens of four staggered prompts, and every pool the engine holds."""
    adapters = kw.pop("adapters", None)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128, page_size=32,
                    prefill_chunk=16, adapters=adapters, **kw)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, V, n).astype(np.int32) for n in (21, 7, 40, 12)]
    aids = ["a0", None, "a1", "a0"] if adapters is not None else [None] * 4
    futs = [eng.submit(p, max_new_tokens=7, adapter_id=a)
            for p, a in zip(prompts, aids)]
    eng.run_until_complete()
    pools = [np.asarray(x) for x in jax.tree_util.tree_leaves(eng.caches)]
    return [f.result(timeout=1) for f in futs], pools


@pytest.mark.parametrize("feature", ["int8_pages", "verify", "lora"])
def test_int8_pages_verify_and_lora_serve_as_under_the_old_rule(
        model, feature, monkeypatch):
    kw = {"int8_pages": dict(cache_dtype="int8"),
          "verify": dict(spec_k=3),
          "lora": {}}[feature]
    if feature == "lora":
        ads = {f"a{i}": LoraAdapter.random(lora_sites(model), rank=4,
                                           seed=100 + i) for i in range(2)}
        kw["adapters"] = AdapterRegistry.from_adapters(model, ads, rank=4)
    before = _qkv_paths()
    new_tokens, new_pools = _serve(model, **dict(kw))
    taken = _qkv_paths()
    assert taken.get("qkv_one_product/plain_linear", 0) \
        > before.get("qkv_one_product/plain_linear", 0)
    with monkeypatch.context() as mp:
        _old_rule(mp)
        if feature == "lora":
            kw["adapters"] = AdapterRegistry.from_adapters(model, ads, rank=4)
        old_tokens, old_pools = _serve(model, **kw)
    assert new_tokens == old_tokens
    assert len(new_pools) == len(old_pools)
    for a, b in zip(new_pools, old_pools):
        assert np.array_equal(a, b)


# ---------------------------------------------------------- the census
_SHAPE = re.compile(r"^\(?(\w+)\[([0-9,]*)\](\{[^}]*\})?")


@pytest.mark.parametrize("kw", [{}, {"spec_k": 2}], ids=["mixed", "chunk_verify"])
def test_paged_programs_read_each_qkv_weight_once(model, kw):
    cfg = model.config
    hidden = cfg.hidden_size
    nq = hidden
    nkv = cfg.num_key_value_heads * hidden // cfg.num_attention_heads
    weights = {hidden * nq, hidden * nkv, hidden * (nq + 2 * nkv)}
    eng = LLMEngine(model, max_batch_slots=4, max_seq_len=128,
                    page_size=16, num_pages=17, prefill_chunk=16, **kw)
    seen = set()
    for jit, args, _ in eng._programs():
        txt = jit.lower(*args).compile().as_text()
        module = _census.module_name(txt)
        rows = _census.per_op_census(txt)
        if not any(r["scope"] == "attention/qkv_proj" for r in rows):
            continue  # the page copies: no layer in them
        seen.add(module)
        comps, _ = _census._computations(txt)
        result = {(c, i["name"]): i["result"]
                  for c, body in comps.items() for i in body}
        products = [r for r in rows if r["scope"] == "attention/qkv_proj"
                    and r["flops"] > 0]
        # one product a layer, and it covers the three weights
        assert len(products) == cfg.num_hidden_layers, (module, products)
        for r in products:
            assert r["flops"] % (2 * hidden * (nq + 2 * nkv)) == 0, (module, r)
        for r in rows:
            if r["scope"] not in ("attention", "attention/qkv_proj") \
                    or r in products:
                continue
            m = _SHAPE.match(result[(r["computation"], r["name"])])
            dims = [int(d) for d in m.group(2).split(",") if d] if m else []
            if hidden not in dims or int(np.prod(dims)) not in weights:
                continue  # an activation, a page pool
            # only the product's own reads: a weight (or the three side by
            # side) as stored, [hidden, n] row-major — never a transposed
            # or head-split copy
            assert dims[0] == hidden and len(dims) == 2 \
                and m.group(3) in (None, "{1,0}"), (module, r["name"], m.group(0))
    want = {"jit_llm_mixed", "jit_llm_decode"} if not kw else {
        "jit_llm_prefill_chunk", "jit_llm_decode", "jit_llm_spec_verify"}
    assert seen == want
