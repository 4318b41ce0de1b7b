"""Operations a DeepSeek-V3-shaped cell's work requires, from shapes and
counters (never from a profile).  Matrix products count 2 x rows x
parameters; the embedding is a gather and counts nothing.

  every layer  q, kv_a, kv_b and o for every token computed (a prefix hit
               computes nothing); kv_b once a token is what either form's
               absorption or expansion of the token's own key costs
  attention    a DECODE (query, key) pair in the absorbed form, 2 H (latent +
               rope + latent) (69,632 as published), by the COUNTED context
               tokens; a prefill chunk of m queries at offset o the LESSER of
               the two forms: absorbed pairs, or expanded pairs 2 H (nope +
               rope + v) plus 2 latent H (nope + v) a context key to expand it
  dense FFN    three matrices of the dense width
  expert FFN   the router (over ALL published experts) and the shared experts
               for every token; the routed experts by the COUNTED pairs whose
               expert is held here, 2 x 3 h F each
  head         2 x h x vocabulary a row
"""
from __future__ import annotations

from benchmark.weights_deepseek_v3 import sizes


def expert_params(cfg):
    """The three matrices of one routed expert."""
    s = sizes(cfg)
    return 3 * s["h"] * s["expert_ffn"]


def attention_token_flops(cfg):
    """The four projections of one layer, a token."""
    s = sizes(cfg)
    H = s["heads"]
    return 2 * (s["h"] * H * (s["nope"] + s["rope"]) + s["h"] * (s["latent"] + s["rope"])
                + s["latent"] * H * (s["nope"] + s["v"]) + H * s["v"] * s["h"])


def pair_ops(cfg):
    """(absorbed a pair, expanded a pair, expanding one context key)."""
    s = sizes(cfg)
    H = s["heads"]
    return (2 * H * (2 * s["latent"] + s["rope"]),
            2 * H * (s["nope"] + s["rope"] + s["v"]),
            2 * s["latent"] * H * (s["nope"] + s["v"]))


def chunk_flops(cfg, offset, queries):
    """Attention of one prefill chunk in one layer: the lesser form."""
    absorbed, expanded, expand = pair_ops(cfg)
    pairs = queries * offset + queries * (queries + 1) // 2
    return min(pairs * absorbed, pairs * expanded + (offset + queries) * expand)


def serve_flops(cfg, tokens, head_rows, held_pairs, decode_ctx_tokens, chunks):
    """`tokens` through the layers, `head_rows` through the output head;
    `held_pairs` the (token, expert) pairs this chip's experts served and
    `decode_ctx_tokens` the context tokens the decode queries attended, both
    counted over all layers; `chunks` [(offset, queries)] the prefill chunks."""
    s = sizes(cfg)
    n_dense, n_moe = s["kinds"].count("D"), s["kinds"].count("E")
    every = 2 * s["h"] * s["router"] + 6 * s["h"] * s["shared_ffn"]
    return (tokens * (s["layers"] * attention_token_flops(cfg)
                      + n_dense * 6 * s["h"] * s["ffn"] + n_moe * every)
            + held_pairs * 2 * expert_params(cfg)
            + decode_ctx_tokens * pair_ops(cfg)[0]
            + s["layers"] * sum(chunk_flops(cfg, o, m) for o, m in chunks)
            + head_rows * 2 * s["h"] * s["vocab"])
