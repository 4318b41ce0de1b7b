"""Least work of the block-sparse decode pass (`sparse_paged_attention`),
from the program's own count of what the published selection picked.

A decode query in one minicpm4 layer reads the K and V rows of its selected
blocks once for each K/V head (the 16 query heads of a head share them) and
does 4 x heads x head_dim operations a key; queries and outputs are 2 x heads
x head_dim elements a query.  The blocks are counted whole (the block that
holds the query is read to its end by any kernel that fetches blocks).  The
counters are the engine's (`stats()["sparse_attention"]["decode"]`), read at
both ends of the traced part.

NOT in this share: the selection (scoring the compressed keys, 1/16 of K, and
the `top_k`) is plain XLA under the `sparse_select` scope; its fusions carry
no name a trace event can be matched by, so neither its bytes nor its time
are counted here.  Prefill chunks attend through a masked XLA pass
(`sparse_chunk_attention` scope) and are no work of this kernel.
"""
from __future__ import annotations

from benchmark.weights_minicpm_sala import sizes

PATTERNS = ("sparse_paged_attention",)
KV_BYTES = 2  # bfloat16 pages
FIELDS = ("sparse_attention.decode.selected_blocks",
          "sparse_attention.decode.layer_calls")


def work(cfg, selected_blocks, layer_calls):
    """(flops, bytes) of `layer_calls` decode queries that selected
    `selected_blocks` blocks in all (both summed over the layers)."""
    s = sizes(cfg)
    keys = selected_blocks * s["sparse"]["block_size"]
    per_q = s["heads"] * s["head_dim"]
    return (4 * per_q * keys,
            2 * s["kv_heads"] * s["head_dim"] * KV_BYTES * keys
            + 2 * per_q * KV_BYTES * layer_calls)


def classes(obs):
    edges = obs.get("traced_counters")
    if not edges or any(f not in edges["after"]["stats"] for f in FIELDS):
        return {}
    sel, calls = (edges["after"]["stats"][f] - edges["before"]["stats"].get(f, 0)
                  for f in FIELDS)
    return {"decode": work(obs["cfg"], sel, calls)}
