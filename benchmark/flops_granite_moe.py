"""Operations a Granite-4.0-H cell with routed experts (Small) requires, from
shapes, the requests' own lengths and the engine's counters (never from a
profile): benchmark/flops_granite_hybrid.py's count for the mixers, the
shared expert (its gated MLP at shared_intermediate_size), the attention and
the tied head, plus in every layer

  router   2 x hidden x the router's experts a token (all of them: a token is
           routed over every expert, held here or not)
  experts  the COUNTED pairs whose expert is held here, 2 x 3 x hidden x
           intermediate_size each (not top_k a token: the absent experts'
           pairs are another chip's work)
"""
from __future__ import annotations

from benchmark import flops_granite_hybrid as _dense
from benchmark.weights_granite_moe import sizes


def expert_params(cfg):
    """The three matrices of one routed expert."""
    s = sizes(cfg)
    return 3 * s["h"] * s["expert_ffn"]


def router_token_flops(cfg):
    s = sizes(cfg)
    return 2 * s["h"] * s["router"]


def serve_flops(cfg, tokens, head_rows, key_pairs, held_pairs):
    """`tokens` through the layers, `head_rows` through the tied head,
    `key_pairs` the keys the computed queries attend in all, `held_pairs`
    the (token, expert) pairs this chip's experts served over all layers."""
    return (_dense.serve_flops(cfg, tokens, head_rows, key_pairs)
            + tokens * sizes(cfg)["layers"] * router_token_flops(cfg)
            + held_pairs * 2 * expert_params(cfg))
