"""Least work of the latent decode pass (`latent_attention`), from the
program's own count of the contexts its decode queries were handed.

A decode query in one layer reads its context's latents ONCE for all heads:
latent + rope values a token in the cache's 2-byte elements (1,152 B as
published), whatever the pool pads a row to and however many rows share a
document (each row's read is counted: a kernel that read a shared document
once for the rows that share it would make this count stale).  It does
2 x heads x (latent + rope + latent) operations a key (69,632: the absorbed
form), and its absorbed query, rotary query and output are heads x (2 latent +
rope) elements a query.  The counters are the engine's
(`stats()["latent_attention"]["decode"]`), read at both ends of the traced
part.

NOT in this share: the absorptions around the kernel (W^K before, W^V after:
XLA matmuls under `latent_absorb`) and a prefill chunk's pass (`latent_chunk`,
plain XLA): their fusions carry no name a trace event can be matched by.
"""
from __future__ import annotations

from benchmark.flops_deepseek_v3 import pair_ops
from benchmark.weights_deepseek_v3 import sizes

PATTERNS = ("latent_attention",)
BYTES = 2  # bfloat16 cache, queries and outputs
FIELDS = ("latent_attention.decode.context_tokens",
          "latent_attention.decode.layer_calls")


def work(cfg, context_tokens, layer_calls):
    """(flops, bytes) of `layer_calls` decode queries over `context_tokens`
    context tokens in all (both summed over the layers)."""
    s = sizes(cfg)
    row = s["latent"] + s["rope"]
    return (pair_ops(cfg)[0] * context_tokens,
            (row * context_tokens
             + s["heads"] * (2 * s["latent"] + s["rope"]) * layer_calls) * BYTES)


def classes(obs):
    edges = obs.get("traced_counters")
    if not edges or any(f not in edges["after"]["stats"] for f in FIELDS):
        return {}
    ctx, calls = (edges["after"]["stats"][f] - edges["before"]["stats"].get(f, 0)
                  for f in FIELDS)
    return {"decode": work(obs["cfg"], ctx, calls)}
