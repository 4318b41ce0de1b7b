"""Operations a cell's work requires, from shapes (never from a profile).

Matrix products count 2 x rows x parameters; the embedding is a gather and
counts nothing.  Causal attention over a query at position p attends p + 1
keys: 4 x heads x head_dim operations a key (QK^T and PV) in each layer.
Training is forward + backward = 3 x forward (bench.py's 6 N tokens +
6 B S^2 h L, with N the matmul parameters); recomputation is not counted.
"""
from __future__ import annotations

from benchmark.weights import sizes


def layer_matmul_params(cfg):
    s = sizes(cfg)
    kv = s["kv_heads"] * s["head_dim"]
    return 2 * s["h"] * s["h"] + 2 * s["h"] * kv + 3 * s["h"] * s["ffn"]


def head_params(cfg):
    s = sizes(cfg)
    return s["h"] * s["vocab"]


def attention_flops(cfg, key_pairs):
    """`key_pairs`: sum over computed query tokens of the keys each attends."""
    s = sizes(cfg)
    return 4 * s["heads"] * s["head_dim"] * key_pairs * s["layers"]


def serve_flops(cfg, tokens, head_rows, key_pairs):
    """`tokens` through the layers, `head_rows` through the output head."""
    s = sizes(cfg)
    return (2 * layer_matmul_params(cfg) * s["layers"] * tokens
            + 2 * head_params(cfg) * head_rows
            + attention_flops(cfg, key_pairs))


def train_flops_per_step(cfg, batch, seq):
    s = sizes(cfg)
    tokens = batch * seq
    n = layer_matmul_params(cfg) * s["layers"] + head_params(cfg)
    causal_pairs = batch * seq * (seq + 1) // 2
    return 6 * n * tokens + 3 * attention_flops(cfg, causal_pairs)
