"""The whole step's share of the chip's peak for a Granite-4.0-H cell with
routed experts: what the window's work required (benchmark/
flops_granite_moe.py: tokens, head rows and attended keys from the requests'
own lengths; the held experts' pairs from the engine's counters over the same
window) over window x chips x peak bf16 FLOP/s.  {"reader": "mfu_granite_moe"}
A program without the counters gives nothing.
"""
from benchmark import flops_granite_moe as flops
from benchmark.readers.mfu_deepseek_v3 import PAIRS, counted


def read(spec, obs):
    pairs = counted(obs["before"], obs["after"], PAIRS)
    if pairs is None:
        return None
    w = obs["work"](*obs["window"])
    need = flops.serve_flops(
        obs["cfg"], w["prefill_tokens"] + w["decode_tokens"], w["head_rows"],
        w["decode_ctx_sum"] + w["prefill_pairs"], pairs)
    if not need:
        return None
    return 100.0 * need / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops"])
