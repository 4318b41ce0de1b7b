"""The whole step's share of the chip's peak for a MiniCPM-SALA cell: what
the window's work required (benchmark/flops_minicpm_sala.py: tokens computed
from the requests' own lengths, prefix hits excluded; the attended and scored
blocks from the engine's counters over the same window) over window x chips x
peak bf16 FLOP/s.  {"reader": "mfu_minicpm_sala"}  A program without the
counters gives nothing.
"""
from benchmark import flops_minicpm_sala as flops

BLOCKS = ("selected_blocks", "context_blocks")


def counted(before, after, field):
    """A sparse-attention counter's change over both programs, or None where
    the program has no such counter."""
    keys = [f"sparse_attention.{p}.{field}" for p in ("decode", "prefill")]
    if any(k not in after["stats"] for k in keys):
        return None
    return sum(after["stats"][k] - before["stats"].get(k, 0) for k in keys)


def read(spec, obs):
    sel, ctx = (counted(obs["before"], obs["after"], f) for f in BLOCKS)
    if sel is None or ctx is None:
        return None
    w = obs["work"](*obs["window"])
    need = flops.serve_flops(
        obs["cfg"], w["prefill_tokens"] + w["decode_tokens"], w["head_rows"],
        sel, ctx)
    if not need:
        return None
    return 100.0 * need / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops"])
