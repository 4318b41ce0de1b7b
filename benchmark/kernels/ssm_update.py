"""Least work of the Mamba-2 single-token state update (`ssm_update`), from
the traffic's lengths.

A decode token in one M layer reads and writes the slot's SSM state once
(heads x head_dim x N float32, 2 MB at the published sizes) and writes its
readout; the update is 5 operations a state element (decay 1, outer product
2, readout 2).  The convolution's 37 KB of state and the [channels] vectors
around the kernel are XLA fusions, not events of this kernel: neither their
bytes nor their time are counted, so the share is the state pass's own.
Prefill chunks run the chunked scan (plain jnp, `ssd_chunk_scan` scope) and
are no work of this kernel.
"""
from __future__ import annotations

from benchmark.weights_nemotron_h import sizes

PATTERNS = ("ssm_update",)


def work(cfg, decode_tokens):
    """(flops, bytes) of `decode_tokens` through every M layer."""
    s = sizes(cfg)
    state = s["m_heads"] * s["m_head_dim"] * s["state"]
    layers = s["pattern"].count("M")
    return (5 * state * decode_tokens * layers,
            (2 * state + s["inner"]) * 4 * decode_tokens * layers)


def classes(obs):
    if obs.get("traced") is None:
        return {}
    w = obs["work"](*obs["traced"])
    return {"decode": work(obs["cfg"], w["decode_tokens"])}
