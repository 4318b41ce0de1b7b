"""Operations a Nemotron-H cell's work requires, from shapes and counters
(never from a profile).  Matrix products count 2 x rows x parameters; the
embedding is a gather and counts nothing.

  M  the two projections; the convolution (2 x taps a channel); the
     recurrence, 5 a state element a token (decay 1, outer product 2,
     readout 2) plus the D skip and the gate
  E  the router (over ALL published experts) and the shared expert for
     every token; the routed experts by the COUNTED pairs whose expert is
     held here, 2 x 2 h F each: what an absent expert would cost is not
     this chip's work
  *  the four projections, and 4 x heads x head_dim a key attended
"""
from __future__ import annotations

from benchmark.weights_nemotron_h import sizes


def expert_params(cfg):
    s = sizes(cfg)
    return 2 * s["h"] * s["ffn"]


def mamba_token_flops(cfg):
    s = sizes(cfg)
    proj = 2 * s["h"] * (2 * s["inner"] + 2 * s["groups"] * s["state"] + s["m_heads"]) \
        + 2 * s["inner"] * s["h"]
    conv = 2 * s["conv_k"] * s["conv_c"]
    return proj + conv + recurrence_token_flops(cfg)


def recurrence_token_flops(cfg):
    """One token's state update and readout in one M layer."""
    s = sizes(cfg)
    return 5 * s["m_heads"] * s["m_head_dim"] * s["state"] + 4 * s["inner"]


def expert_layer_token_flops(cfg):
    """Router and shared expert: every token, whoever holds its experts."""
    s = sizes(cfg)
    return 2 * s["h"] * s["router"] + 4 * s["h"] * s["shared_ffn"]


def attention_token_flops(cfg):
    s = sizes(cfg)
    return 4 * s["h"] * (s["heads"] + s["kv_heads"]) * s["head_dim"]


def serve_flops(cfg, tokens, head_rows, key_pairs, held_pairs):
    """`tokens` through the layers, `head_rows` through the output head,
    `key_pairs` the keys the computed queries attend in all, `held_pairs`
    the (token, expert) pairs this chip's experts served, over all layers."""
    s = sizes(cfg)
    n = {k: s["pattern"].count(k) for k in "ME*"}
    return (tokens * (n["M"] * mamba_token_flops(cfg)
                      + n["E"] * expert_layer_token_flops(cfg)
                      + n["*"] * attention_token_flops(cfg))
            + held_pairs * 2 * expert_params(cfg)
            + 4 * s["heads"] * s["head_dim"] * key_pairs * n["*"]
            + head_rows * 2 * s["h"] * s["vocab"])
