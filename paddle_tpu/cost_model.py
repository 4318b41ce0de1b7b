"""paddle.cost_model — per-program cost estimation.

Ref: python/paddle/cost_model/cost_model.py:23 (CostModel.profile_measure runs
the program under the profiler and reports per-op time).

TPU-native: XLA already computes an analytical cost model for every compiled
executable; `CostModel.static_cost` surfaces it (flops / bytes accessed /
estimated optimal seconds) from `jit(fn).lower().compile().cost_analysis()`,
and `profile_measure` wall-clocks the compiled program.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from .tensor.tensor import Tensor

__all__ = ["CostModel", "peak_flops_per_device", "peak_hbm_bytes_per_sec"]

#: Dense bf16 peak FLOP/s per chip, by device_kind substring (public TPU
#: spec sheets; the MFU denominator).  Unknown kinds (CPU hosts, new
#: generations) return 0.0 unless PADDLE_TPU_PEAK_FLOPS overrides.
_PEAK_FLOPS_BY_KIND = (
    # jax reports the "lite" chips as e.g. "TPU v5 lite" / "TPU v5e"
    # depending on runtime version — match both spellings
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def peak_flops_per_device(device=None) -> float:
    """Peak dense FLOP/s of one attached device (0.0 when unknown).

    ``PADDLE_TPU_PEAK_FLOPS`` overrides — the escape hatch for CPU hosts,
    dryruns projecting a different pod, and future device kinds.  Used by
    the train-step instrumentation to turn HLO-estimated step FLOPs into an
    MFU gauge (`train_mfu_ratio`).
    """
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        kind = (device or jax.devices()[0]).device_kind.lower()
    except Exception:
        return 0.0
    for sub, peak in _PEAK_FLOPS_BY_KIND:
        if sub in kind:
            return peak
    return 0.0


#: HBM bandwidth per chip in bytes/s, by device_kind substring (public TPU
#: spec sheets; the roofline's memory-term denominator).  Same shape and
#: lookup order as _PEAK_FLOPS_BY_KIND.
_PEAK_HBM_BW_BY_KIND = (
    ("v6 lite", 1640e9), ("v6e", 1640e9),
    ("v5p", 2765e9), ("v5 lite", 819e9), ("v5e", 819e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
)

#: One-shot microbench cache: the measured fallback touches hundreds of MB
#: of HBM, so it runs at most once per process.
_MEASURED_HBM_BW: float | None = None


def peak_hbm_bytes_per_sec(device=None, measure=False) -> float:
    """Peak HBM bytes/s of one attached device (0.0 when unknown).

    Same contract as :func:`peak_flops_per_device`:
    ``PADDLE_TPU_PEAK_HBM_BW`` overrides everything, then the device-kind
    spec table.  When the kind is unknown (CPU hosts, new generations), a
    microbench fallback — timing a large on-device ``jnp.copy`` — can
    stand in, but ONLY behind explicit opt-in (``measure=True`` or
    ``PADDLE_TPU_MEASURE_HBM_BW=1``): tier-1 predictions must stay
    deterministic, and a measured "peak" silently becoming the roofline
    denominator would make every residual ratio ~1.0 by construction.
    The measurement is cached for the process.
    """
    env = os.environ.get("PADDLE_TPU_PEAK_HBM_BW")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        dev = device or jax.devices()[0]
        kind = dev.device_kind.lower()
    except Exception:
        return 0.0
    for sub, peak in _PEAK_HBM_BW_BY_KIND:
        if sub in kind:
            return peak
    if measure or os.environ.get("PADDLE_TPU_MEASURE_HBM_BW") == "1":
        return _measure_hbm_bytes_per_sec(dev)
    return 0.0


def _measure_hbm_bytes_per_sec(device, mbytes=256, reps=4) -> float:
    """Time a large device-to-device copy: ``mbytes`` read + ``mbytes``
    written per rep, best-of-``reps`` (bandwidth microbenches take the max:
    stragglers are scheduling noise, not the memory system)."""
    global _MEASURED_HBM_BW
    if _MEASURED_HBM_BW is not None:
        return _MEASURED_HBM_BW
    n = mbytes * (1 << 20) // 4
    src = jax.device_put(jnp.zeros((n,), jnp.float32), device)
    copy = jax.jit(lambda x: jnp.copy(x))  # runs where the operand lives
    jax.block_until_ready(copy(src))  # compile + warm
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(copy(src))
        dt = time.perf_counter() - t0
        if dt > 0:
            best = max(best, 2 * n * 4 / dt)
    _MEASURED_HBM_BW = best
    return best


def _unwrap(args):
    return tuple(a._value if isinstance(a, Tensor) else a for a in args)


class CostModel:
    def static_cost(self, fn, *args, **kwargs):
        """Compile `fn` on example args and return XLA's analytical cost:
        {'flops': ..., 'bytes accessed': ..., 'optimal_seconds': ...} (keys as
        reported by the backend; missing entries are 0.0)."""
        lowered = jax.jit(fn).lower(*_unwrap(args), **kwargs)
        out = dict(lowered.compile().cost_analysis() or {})
        for key in ("flops", "bytes accessed", "optimal_seconds"):
            out.setdefault(key, 0.0)
        return out

    def profile_measure(self, fn, *args, steps=10, warmup=3, **kwargs):
        """Wall-clock the compiled program (ref profile_measure returns
        measured per-op cost; here the whole fused program is the op).
        Compiles ONCE: the same executable serves both the cost analysis
        and the timed calls."""
        raw = _unwrap(args)
        compiled = jax.jit(fn).lower(*raw, **kwargs).compile()
        analysis = dict(compiled.cost_analysis() or {})
        r = None
        for _ in range(warmup):
            r = compiled(*raw, **kwargs)
        jax.tree.map(lambda x: jax.block_until_ready(x), r)
        t0 = time.perf_counter()
        for _ in range(steps):
            r = compiled(*raw, **kwargs)
        jax.tree.map(lambda x: jax.block_until_ready(x), r)
        dt = (time.perf_counter() - t0) / steps
        return {"time_s": dt,
                "flops": analysis.get("flops", 0.0),
                "achieved_flops_per_s": (analysis.get("flops", 0.0) / dt) if dt > 0 else 0.0,
                "bytes_accessed": analysis.get("bytes accessed", 0.0)}
