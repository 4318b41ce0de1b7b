"""Least work of the Mamba-2 single-token state update (`ssm_update`) in a
Granite-4.0-H cell, from the traffic's lengths: benchmark/kernels/
ssm_update.py's accounting (which reads Nemotron-H's pattern) over the
config's `mamba` layers.  A decode token in one such layer reads and writes
the slot's SSM state once (heads x head_dim x N float32, 2 MB at the
published sizes) and writes its readout; 5 operations a state element.  The
convolution's state and the vectors around the kernel are XLA fusions, not
its events; prefill chunks run the chunked scan, no work of this kernel.
"""
from __future__ import annotations

from benchmark.weights_granite_hybrid import sizes

PATTERNS = ("ssm_update",)


def work(cfg, decode_tokens):
    """(flops, bytes) of `decode_tokens` through every mamba layer."""
    s = sizes(cfg)
    state = s["m_heads"] * s["m_head_dim"] * s["state"]
    return (5 * state * decode_tokens * s["n_mamba"],
            (2 * state + s["inner"]) * 4 * decode_tokens * s["n_mamba"])


def classes(obs):
    if obs.get("traced") is None:
        return {}
    w = obs["work"](*obs["traced"])
    return {"decode": work(obs["cfg"], w["decode_tokens"])}
