"""The whole step's share of the chip's peak for a Granite-4.0-H cell: what
the window's work required (benchmark/flops_granite_hybrid.py: tokens, head
rows and attended keys from the requests' own lengths) over window x chips x
peak bf16 FLOP/s.  {"reader": "mfu_granite_hybrid"}
"""
from benchmark import flops_granite_hybrid as flops


def read(spec, obs):
    w = obs["work"](*obs["window"])
    need = flops.serve_flops(
        obs["cfg"], w["prefill_tokens"] + w["decode_tokens"], w["head_rows"],
        w["decode_ctx_sum"] + w["prefill_pairs"])
    if not need:
        return None
    return 100.0 * need / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops"])
