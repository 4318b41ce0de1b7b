"""The whole step's share of the chip's peak for a DeepSeek-V3-shaped cell:
what the window's work required (benchmark/flops_deepseek_v3.py: tokens and
prefill chunks from the requests' own lengths, prefix hits excluded; the held
experts' pairs and the decode queries' context tokens from the engine's
counters over the same window) over window x chips x peak bf16 FLOP/s.
{"reader": "mfu_deepseek_v3"}  A program without the counters gives nothing.
"""
from benchmark import flops_deepseek_v3 as flops

PAIRS = ("moe.decode.pairs_held", "moe.prefill.pairs_held")
CONTEXT = ("latent_attention.decode.context_tokens",)


def counted(before, after, keys):
    """The counters' change between two snapshots, summed, or None where the
    program has no such counter."""
    if any(k not in after["stats"] for k in keys):
        return None
    return sum(after["stats"][k] - before["stats"].get(k, 0) for k in keys)


def read(spec, obs):
    pairs, ctx = (counted(obs["before"], obs["after"], k) for k in (PAIRS, CONTEXT))
    if pairs is None or ctx is None:
        return None
    w = obs["work"](*obs["window"])
    need = flops.serve_flops(
        obs["cfg"], w["prefill_tokens"] + w["decode_tokens"], w["head_rows"],
        pairs, ctx, w["chunks"])
    if not need:
        return None
    return 100.0 * need / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops"])
