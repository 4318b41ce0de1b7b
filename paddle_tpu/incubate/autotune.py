"""paddle.incubate.autotune (ref: python/paddle/incubate/autotune.py:75
set_config over the phi autotune cache, paddle/phi/kernels/autotune/).

The reference autotunes cuDNN algorithm choice per op signature.  The TPU
analog: XLA already autotunes fusions, so the tunable surface here is the
Pallas kernel launch configuration — flash attention block sizes are measured
per (seq_q, seq_k, head_dim) signature on first use and cached, exactly the
phi AlgorithmsCache pattern (kernels/autotune/cache.h).
"""
from __future__ import annotations

import json
import time

__all__ = ["set_config", "enable_autotune", "disable_autotune",
           "flash_attention_block_cache", "tune_flash_attention"]

_CONFIG = {
    "kernel": {"enable": False, "tuning_range": [1, 10]},
    "layout": {"enable": False},
    "dataloader": {"enable": False},
}
# (Sq, Sk, D, causal) -> (bq, bk); measured on first use when enabled
flash_attention_block_cache: dict = {}


def set_config(config=None):
    """Ref autotune.py:75 — dict or JSON file path with kernel/layout/
    dataloader sections."""
    global _CONFIG
    if config is None:
        for sec in _CONFIG.values():
            sec["enable"] = True
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    for key, val in config.items():
        if key not in _CONFIG:
            raise ValueError(f"unknown autotune section {key!r} "
                             f"(known: {sorted(_CONFIG)})")
        _CONFIG[key].update(val)


def enable_autotune():
    _CONFIG["kernel"]["enable"] = True


def disable_autotune():
    _CONFIG["kernel"]["enable"] = False


def kernel_autotune_enabled():
    return _CONFIG["kernel"]["enable"]


def measure_callable(fn, steps=3, warmup=1):
    """Best-of-`steps` wall time of `fn()` after `warmup` calls — the shared
    measuring primitive behind kernel autotune and the auto-parallel
    planner's measured rerank (ref tuner/profiler.py measuring candidates)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(steps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def tune_flash_attention(q, k, v, causal, scale, candidates=None, steps=20):
    """Measure candidate (block_q, block_k) configs for this attention
    signature and cache the fastest (phi AlgorithmsCache analog).

    Returns the chosen (bq, bk).  Called by ops.flash_attention when kernel
    autotune is enabled; measurement uses the real kernel on the attached
    backend and blocks on ONE scalar readback per window.  `steps` kernels
    run per window so candidate deltas dwarf the cost of that one dispatch
    and sync."""
    import importlib

    import jax
    import jax.numpy as jnp

    # the ops package re-exports the flash_attention FUNCTION under the same
    # name as its module; load the module explicitly
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")

    Sq, Sk, D = q.shape[-2], k.shape[-2], q.shape[-1]
    key = (Sq, Sk, D, bool(causal))
    if key in flash_attention_block_cache:
        return flash_attention_block_cache[key]

    if candidates is None:
        opts = [b for b in (128, 256, 512) if Sq % b == 0 and Sk % b == 0]
        candidates = [(b, b) for b in opts] or [(fa._auto_block(Sq),
                                                fa._auto_block(Sk))]
    if len(candidates) == 1:
        # nothing to choose between — skip the warmup compile + timed sync
        flash_attention_block_cache[key] = candidates[0]
        return candidates[0]
    best, best_t, last_err = None, float("inf"), None
    for bq, bk in candidates:
        try:
            f = jax.jit(lambda a, b_, c: fa._flash_bhsd(
                a, b_, c, causal, scale, bq, bk, fa._interpret_default()))
            out = f(q, k, v)
            float(jnp.sum(out[..., :1]).astype(jnp.float32))  # compile+sync
            t0 = time.perf_counter()
            for _ in range(steps):
                out = f(q, k, v)
            float(jnp.sum(out[..., :1]).astype(jnp.float32))
            dt = time.perf_counter() - t0
            if dt < best_t:
                best, best_t = (bq, bk), dt
        except Exception as e:
            last_err = e
            continue
    if best is None:
        raise RuntimeError(
            f"flash-attention autotune: every candidate failed for signature "
            f"{key}; last error: {last_err!r}")
    flash_attention_block_cache[key] = best
    return best
