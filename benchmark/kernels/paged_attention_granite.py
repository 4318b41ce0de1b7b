"""Least work of the ragged paged-attention kernel in a Granite-4.0-H cell:
benchmark/kernels/paged_attention.py's accounting (which reads a Llama
config's sizes) over the config's `attention` layers, at head_dim = hidden /
heads = 64 and the cache's 2-byte elements.  The pool holds two 64-wide
heads a 128-lane row and the kernel scores zero-padded queries against the
whole row; what is counted is what the attention requires, the heads' own
bytes and operations, not the kernel's plan.
"""
from __future__ import annotations

from benchmark.weights_granite_hybrid import sizes

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
    L = s["n_attn"]
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
    if obs.get("traced") is None:
        return {}
    w = obs["work"](*obs["traced"])
    return work(obs["cfg"], w["decode_tokens"], w["decode_ctx_sum"], w["chunks"],
                w["prefill_pairs"])
