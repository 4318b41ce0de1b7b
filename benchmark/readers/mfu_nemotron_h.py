"""The whole step's share of the chip's peak for a Nemotron-H cell: what the
window's work required (benchmark/flops_nemotron_h.py: tokens and attended
keys from the requests' own lengths, the held experts' pairs from the
engine's counters over the same window) over window x chips x peak bf16
FLOP/s.  {"reader": "mfu_nemotron_h"}  A program without the counters gives
nothing.
"""
from benchmark import flops_nemotron_h as flops

PAIRS = ("moe.decode.pairs_held", "moe.prefill.pairs_held")


def held_pairs(before, after):
    """Pairs the held experts served between two snapshots, or None where
    the program has no such counter."""
    if any(k not in after["stats"] for k in PAIRS):
        return None
    return sum(after["stats"][k] - before["stats"].get(k, 0) for k in PAIRS)


def read(spec, obs):
    pairs = held_pairs(obs["before"], obs["after"])
    if pairs is None:
        return None
    w = obs["work"](*obs["window"])
    need = flops.serve_flops(
        obs["cfg"], w["prefill_tokens"] + w["decode_tokens"], w["head_rows"],
        w["decode_ctx_sum"] + w["prefill_pairs"], pairs)
    if not need:
        return None
    return 100.0 * need / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops"])
