"""Least work of the lightning-attention single-token state update
(`lightning_update`), from the program's count of decode queries.

A decode token in one lightning layer reads and writes the slot's state once
(32 heads x 128 x 128 float32 = 2 MiB) and its q, k, v and readout rows; the
update is 5 operations a state element (decay 1, k^T v 2, q S 2).  The
projections, norms and the rotary embedding around the kernel are XLA fusions,
not events of this kernel: neither their bytes nor their time are counted.
Prefill chunks run the chunked scan (plain jnp, `lightning_chunk_scan` scope)
and are no work of this kernel.

Decode tokens of the traced part = the sparse layers' decode query calls over
the number of sparse layers (every real decode row passes each layer once):
the engine's counter, read at both ends of the traced part.
"""
from __future__ import annotations

from benchmark.weights_minicpm_sala import sizes

PATTERNS = ("lightning_update",)
CALLS = "sparse_attention.decode.layer_calls"


def work(cfg, decode_tokens):
    """(flops, bytes) of `decode_tokens` through every lightning layer."""
    s = sizes(cfg)
    state = s["l_heads"] * s["l_head_dim"] ** 2
    rows = 4 * s["l_heads"] * s["l_head_dim"]   # q, k, v in, the readout out
    return (5 * state * decode_tokens * s["n_lightning"],
            (2 * state + rows) * 4 * decode_tokens * s["n_lightning"])


def classes(obs):
    edges = obs.get("traced_counters")
    if not edges or CALLS not in edges["after"]["stats"]:
        return {}
    calls = edges["after"]["stats"][CALLS] - edges["before"]["stats"].get(CALLS, 0)
    return {"decode": work(obs["cfg"], calls / sizes(obs["cfg"])["n_sparse"])}
