"""Least work of the gated routed experts (`moe_glu_experts`), from the
engine's counters over the traced part of the window.

benchmark/kernels/moe_experts.py with three matrices an expert: each (token,
expert) pair whose expert is held here costs 2 x 3 h F operations; an expert
that any real row chose has its three matrices read once a layer call (3 h F
elements); a pair's row is read and its result written once, h elements each,
all in the weights' 2-byte elements.  Decode calls and prefill chunks are two
classes: a tick's few rows an expert are bound by the bytes, a chunk's many
may not be.
"""
from __future__ import annotations

from benchmark.flops_deepseek_v3 import expert_params
from benchmark.weights_deepseek_v3 import sizes

PATTERNS = ("moe_glu_experts",)
BYTES = 2  # bf16 weights and rows


def work(cfg, pairs, touched):
    """(flops, bytes) of `pairs` held pairs over `touched` experts fetched."""
    per_expert = expert_params(cfg)
    return (2 * pairs * per_expert,
            (touched * per_expert + 2 * pairs * sizes(cfg)["h"]) * BYTES)


def classes(obs):
    edges = obs.get("traced_counters")
    if not edges:
        return {}
    out = {}
    for prog in ("decode", "prefill"):
        d = {}
        for k in ("pairs_held", "experts_touched"):
            key = f"moe.{prog}.{k}"
            if key not in edges["after"]["stats"]:
                return {}
            d[k] = edges["after"]["stats"][key] - edges["before"]["stats"].get(key, 0)
        out[prog] = work(obs["cfg"], d["pairs_held"], d["experts_touched"])
    return out
