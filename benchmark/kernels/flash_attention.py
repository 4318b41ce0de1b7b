"""Least work of causal flash attention in one training step, from shapes.

Forward: QK^T and PV.  Backward needs dV, dP, dQ and dK, four products of the
same size; recomputing the scores inside the backward kernels is the
implementation's choice and is not counted.  A product over the causal half is
2 x heads x head_dim x pairs operations.  Bytes: forward reads q, k, v and
writes o; backward reads q, k, v, o, do and writes dq, dk, dv, in bf16.
"""
from __future__ import annotations

from benchmark.weights import sizes

PATTERNS = ("flash_fwd", "flash_dq", "flash_dkv")


def work(cfg, batch, seq):
    s = sizes(cfg)
    pairs = batch * seq * (seq + 1) // 2
    flops = 6 * 2 * s["heads"] * s["head_dim"] * pairs * s["layers"]
    q = batch * seq * s["heads"] * s["head_dim"] * 2
    kv = batch * seq * s["kv_heads"] * s["head_dim"] * 2
    nbytes = ((2 * q + 2 * kv) + (4 * q + 4 * kv)) * s["layers"]
    return {"step": (flops, nbytes)}


def classes(obs):
    """The attention work of the steps that ran in the traced part."""
    t = obs["traffic"]
    flops, nbytes = work(obs["cfg"], t["batch"], t["seq"])["step"]
    n = obs["steps_traced"]
    return {"step": (flops * n, nbytes * n)}
