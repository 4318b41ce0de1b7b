"""Operations a MiniCPM-SALA cell's work requires, from shapes and counters
(never from a profile).  Matrix products count 2 x rows x parameters; the
embedding is a gather and counts nothing.

  every layer     the mixer's projections and the feed-forward's three
                  matrices, for every token computed (a prefix hit computes
                  nothing)
  lightning-attn  5 a state element a token (decay 1, k^T v 2, q S 2): 32
                  heads x 128 x 128
  minicpm4        4 x heads x head_dim a key attended, the keys being those of
                  the blocks the program COUNTED as selected (whole blocks: the
                  block that holds the query is counted whole too); the
                  selection itself 2 x heads x head_dim a compressed key of
                  the context (block_size / kernel_stride a block)
  head            2 x h x vocabulary a row
"""
from __future__ import annotations

from benchmark.weights_minicpm_sala import LIGHTNING, SPARSE, layer_shapes, sizes


def layer_token_flops(cfg, kind):
    """The matrices of one layer of `kind`, a token."""
    return 2 * sum(a * b for a, b in
                   (s for s in layer_shapes(cfg, kind).values() if len(s) == 2))


def state_token_flops(cfg):
    """One token's state update and readout in one lightning layer."""
    s = sizes(cfg)
    return 5 * s["l_heads"] * s["l_head_dim"] ** 2


def serve_flops(cfg, tokens, head_rows, selected_blocks, context_blocks):
    """`tokens` through the layers, `head_rows` through the output head;
    `selected_blocks` / `context_blocks`: the sparse layers' counters, summed
    over (query, layer)."""
    s = sizes(cfg)
    sp = s["sparse"]
    per_key = s["heads"] * s["head_dim"]
    return (tokens * (s["n_lightning"] * (layer_token_flops(cfg, LIGHTNING)
                                          + state_token_flops(cfg))
                      + s["n_sparse"] * layer_token_flops(cfg, SPARSE))
            + selected_blocks * sp["block_size"] * 4 * per_key
            + context_blocks * (sp["block_size"] // sp["kernel_stride"]) * 2 * per_key
            + head_rows * 2 * s["h"] * s["vocab"])
