"""Least work of the ragged paged-attention kernel, from the traffic's lengths.

One kernel (`paged_attention`) serves decode ticks (one query a slot) and
prefill chunks (up to `prefill_chunk` queries of one request).  What the
attention REQUIRES, whatever implements it:

  decode   a token at context c reads c keys and c values of every KV head
           once (the query heads of a group share them) and does
           4 x heads x head_dim operations a key
  chunk    m queries at offset o read o + m keys and values once and attend
           o + i + 1 keys each

in every layer, in the cache's 2-byte elements; queries and outputs are
2 x heads x head_dim elements a token.  Pages are read whole by a real kernel;
the partial last page is not counted: this is the floor, not the kernel's plan.
"""
from __future__ import annotations

from benchmark.weights import sizes

PATTERNS = ("paged_attention",)
KV_BYTES = 2  # bf16 pages


def work(cfg, decode_tokens, decode_ctx_sum, chunks, prefill_pairs=None):
    """(flops, bytes) per class: {"decode": (f, b), "prefill": (f, b)}.
    `chunks` is [(offset, queries)]; `prefill_pairs` the keys their queries
    attend in all, worked out here if not given."""
    s = sizes(cfg)
    per_key_flops = 4 * s["heads"] * s["head_dim"]
    per_key_bytes = 2 * s["kv_heads"] * s["head_dim"] * KV_BYTES
    per_tok_bytes = 2 * s["heads"] * s["head_dim"] * KV_BYTES
    L = s["layers"]
    dec = (per_key_flops * decode_ctx_sum * L,
           (per_key_bytes * decode_ctx_sum + per_tok_bytes * decode_tokens) * L)
    pairs = prefill_pairs if prefill_pairs is not None else \
        sum(m * o + m * (m + 1) // 2 for o, m in chunks)
    keys = sum(o + m for o, m in chunks)
    toks = sum(m for _, m in chunks)
    pre = (per_key_flops * pairs * L,
           (per_key_bytes * keys + per_tok_bytes * toks) * L)
    return {"decode": dec, "prefill": pre}


def classes(obs):
    """The attention work of the traced part of the window."""
    w = obs["work"](*obs["traced"])
    return work(obs["cfg"], w["decode_tokens"], w["decode_ctx_sum"], w["chunks"],
                w["prefill_pairs"])
