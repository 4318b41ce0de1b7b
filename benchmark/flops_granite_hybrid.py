"""Operations a Granite-4.0-H cell's work requires, from shapes and the
requests' own lengths (never from a profile).  Matrix products count
2 x rows x parameters; the embedding is a gather and counts nothing, and the
head, which is the same [vocab, hidden] table, counts as the product it is.

  mamba      the two projections; the convolution (2 x taps a channel); the
             recurrence, 5 a state element a token (decay 1, outer product 2,
             readout 2) plus the D skip and the gate
  attention  the four projections, and 4 x heads x head_dim a key attended
  both       the gated MLP, 3 x hidden x shared_intermediate_size parameters
"""
from __future__ import annotations

from benchmark.weights_granite_hybrid import sizes


def recurrence_token_flops(cfg):
    """One token's state update and readout in one mamba layer."""
    s = sizes(cfg)
    return 5 * s["m_heads"] * s["m_head_dim"] * s["state"] + 4 * s["inner"]


def mamba_token_flops(cfg):
    s = sizes(cfg)
    proj = 2 * s["h"] * (2 * s["inner"] + 2 * s["groups"] * s["state"] + s["m_heads"]) \
        + 2 * s["inner"] * s["h"]
    return proj + 2 * s["conv_k"] * s["conv_c"] + recurrence_token_flops(cfg)


def attention_token_flops(cfg):
    s = sizes(cfg)
    return 4 * s["h"] * (s["heads"] + s["kv_heads"]) * s["head_dim"]


def mlp_token_flops(cfg):
    s = sizes(cfg)
    return 2 * 3 * s["h"] * s["ffn"]


def serve_flops(cfg, tokens, head_rows, key_pairs):
    """`tokens` through the layers, `head_rows` through the tied head,
    `key_pairs` the keys the computed queries attend in all."""
    s = sizes(cfg)
    return (tokens * (s["n_mamba"] * mamba_token_flops(cfg)
                      + s["n_attn"] * attention_token_flops(cfg)
                      + s["layers"] * mlp_token_flops(cfg))
            + 4 * s["heads"] * s["head_dim"] * key_pairs * s["n_attn"]
            + head_rows * 2 * s["h"] * s["vocab"])
