"""A kernel's share of its roofline in the traced part of the window: the
least time the chip could take for the work the traffic required of it
(benchmark/kernels/<kernel>.py) over the summed device time of the events
whose names match.  {"reader": "roofline", "kernel": "<file under kernels>"}
Nothing to read (no trace, or no such event) gives nothing, never 0.
"""
import importlib

from benchmark import kernels, reduce_trace


def read(spec, obs):
    red = obs.get("trace")
    if red is None:
        return None
    k = importlib.import_module(f"benchmark.kernels.{spec['kernel']}")
    spent = reduce_trace.seconds_matching(red["ops"], k.PATTERNS)
    if spent <= 0:
        return None
    least, _ = kernels.least_seconds(k.classes(obs), obs["peak"], obs["chips"])
    if least <= 0:
        return None
    return 100.0 * least / spent
