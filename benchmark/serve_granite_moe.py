"""A served cell of a Granite-4.0-H decoder with routed experts (Small):
Mamba-2 and NoPE attention layers, each with routed experts beside a shared
expert, muP multipliers, a tied head, one chip's share of the experts.

benchmark/serve_granite.py with another builder, other weights and another
reference.  Imported unchanged: serve.py's recorder, closed and open loops,
sampling of finished requests and work between two instants, serve_hybrid's
trace window and set-up requests, and serve_granite's held-state bytes (the
same Mamba-2 keys).  Copied, because serve_granite's take Micro's builder,
weights and reference by name: `run` (the same steps; the line's `extra`
reports the experts' counters beside the attention dispatch) and `compare`
(the same two gap limits and the held state bytes, with the controls as a
list: benchmark/reference/granite_moe_ref's).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import loadgen, serve
from benchmark import weights_granite_moe as W
from benchmark.serve_granite import state_bytes
from benchmark.serve_hybrid import _TraceWindow, _warm_up


def build_model(cfg, seed=None):
    """`GraniteHybridForCausalLM` at the config file's sizes, in its dtype,
    holding the experts `share.experts_held` of the router's, with the
    seeded weights when `seed` is given."""
    import paddle_tpu as paddle
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)

    s = W.sizes(cfg)
    paddle.seed(0)
    keys = {k: cfg[k] for k in GraniteHybridConfig.__dataclass_fields__
            if k in cfg}
    keys.update(num_local_experts=s["router"], experts_held=s["held"])
    model = GraniteHybridForCausalLM(
        GraniteHybridConfig(**keys, dtype=cfg["torch_dtype"]))
    model.eval()
    if seed is not None:
        W.load_into(model, cfg, seed)
    return model


def build_engine(cfg, job, seed, store_capacity):
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.observability.tracing import Tracer, TraceStore

    model = build_model(cfg, seed)
    # every trace is kept: the per-request times come from their spans
    tracer = Tracer(store=TraceStore(capacity=store_capacity, sample_every=1))
    eng = LLMEngine(model, kv_layout="paged", tracer=tracer, **job["engine"])
    return model, eng, tracer


def run(cell, seed, seconds, trace, clock0, log):
    """Returns (end_to_end metrics dict, obs for the readers, check numbers)."""
    import jax

    cfg, job, traffic = cell["config"], cell["job"], cell["traffic"]
    reqs, prefixes = loadgen.requests(traffic, cfg["vocab_size"], seed, seconds)
    if prefixes:
        raise ValueError("a model with recurrent state shares no prefix: the "
                         "traffic must not ask for any")
    limit = job["engine"]["max_seq_len"] - 1
    worst = max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs)
    if worst > limit:
        raise ValueError(f"traffic asks for {worst} tokens, engine holds {limit}")
    model, eng, tracer = build_engine(cfg, job, seed, len(reqs) + 64)
    log(f"engine built: {W.n_params(cfg) / 1e9:.3f} B parameters")
    log(f"warmup() took {eng.warmup():.1f} s")
    eng.start()
    try:
        _warm_up(eng, cfg, seed, limit)
        rec = serve._Recorder(eng, "r")
        gc.collect()
        gc.freeze()  # the model's objects never die: keep the collector off them

        snapshot = lambda: {"registry": serve.registry_snapshot(),  # noqa: E731
                            "stats": serve._flatten(eng.stats())}
        window = _TraceWindow(job["trace_seconds"], seconds, snapshot) if trace else None
        before = snapshot()
        t0 = time.perf_counter()
        setup_s = t0 - clock0
        t1 = t0 + seconds
        if window:
            window.start(t0)
        if traffic["loop"] == "open":
            serve._offer_open(rec, reqs, t0, t1)
        else:
            serve._offer_closed(rec, reqs, traffic["clients"], t1)
        t_close = time.perf_counter()
        after = snapshot()
        drained = rec.wait_all(t_close + serve.DRAIN_SECONDS)
        t_gave_up = time.perf_counter()
        red = window.finish() if window else None
        stats = eng.stats()
    finally:
        eng.stop()
    mem = [d.memory_stats() or {}
           for d in jax.local_devices()[:cell["cell"]["chips"]]]
    records = rec.records
    for r in records:
        serve._attach_spans(r, tracer.store)
    log(f"window closed: {len(records)} submitted, drained={drained}")

    ok = [r for r in records if r["error"] is None and r["done"] is not None
          and r["tokens"] is not None and r["first_token"] is not None]
    failed = len(records) - len(ok)
    late = t_gave_up - t0
    ttft = [r["first_token"] - r["due"] for r in ok]
    in_window = [r for r in ok if r["done"] <= t1]
    e2e = {"setup_s": setup_s,
           "out_tokens_per_s": sum(len(r["tokens"]) for r in in_window) / seconds}
    obs = {"kind": "serve_granite_moe", "cfg": cfg, "traffic": traffic,
           "window": (t0, t1), "window_s": seconds, "before": before,
           "after": after, "ok": ok, "trace": red, "traced": None,
           "traced_counters": None,
           "work": lambda a, b: serve.work_between(records, a, b),
           "memory_peak_bytes": max(m.get("peak_bytes_in_use", 0) for m in mem)}
    if window:
        # as serve.py: counters, spans and clocks over the part of the window
        # BEFORE the profiler starts, the device over the traced part
        obs.update(window=(t0, window.split), window_s=window.split - t0,
                   after=window.snapshot, traced=window.bounds,
                   traced_counters={"before": window.edges[0],
                                    "after": window.edges[1]},
                   ok=[r for r in ok if r["due"] < window.split])

    sample = serve._sample(ok, job["check_requests"], seed)
    del eng, model, tracer, rec
    gc.unfreeze()
    gc.collect()
    check = compare(cfg, seed, sample, job["check_pad_to"], job["limits"],
                    job.get("control"), held_state=(
                        stats["recurrent_state"]["bytes"],
                        state_bytes(cfg, job["engine"]["max_batch_slots"])))
    check["attempted"], check["failed"] = len(records), failed
    check["extra"] = {
        "drain_s": t_gave_up - t_close, "done_in_window": len(in_window),
        "queue_depth_at_close": after["stats"].get("queue_depth"),
        "active_slots_at_close": after["stats"].get("active_slots"),
        "ttft_p50_ms": serve._ms(serve.percentile(ttft, 0.5, failed, late)),
        "bytes_in_use_at_close": max(m.get("bytes_in_use", 0) for m in mem),
        "cache_kinds": stats["cache_kinds"],
        "recurrent_state": stats["recurrent_state"],
        "moe": stats["moe"],
        # the attention calls' paths, by trace-time dispatch (process-wide)
        "attn_dispatch": stats["attn_dispatch"],
    }
    check["extra"].update(check.pop("controls", {}))
    return e2e, obs, check


def compare(cfg, seed, sample, pad_to, limits, controls=None, held_state=None):
    """The numbers `correct` is decided on, each beside its limit: the mean
    and the 99.5th percentile of the served tokens' logit gaps (as
    serve_granite.compare), and the SSM state's bytes, which the gaps cannot
    see.  `held_state` is (bytes of recurrent state the engine reported,
    bytes the configuration states); `controls` names
    benchmark/reference/granite_moe_ref's controls to read beside (a control
    run: the readings land in the line's `extra`)."""
    from benchmark.reference import granite_moe_ref

    if not sample:
        return {"correct": False, "numbers": {"sampled_requests": [0, ">=1"]}}
    pairs = [(r["prompt"], r["tokens"]) for r in sample]
    if isinstance(controls, str):
        controls = [controls]
    gaps, ctl = granite_moe_ref.served_gap(cfg, seed, pairs, pad_to,
                                           tuple(controls or ()))
    vocab_ok = all(0 <= int(t) < cfg["vocab_size"] for _, o in pairs for t in o)
    length_ok = all(len(r["tokens"]) == r["max_new_tokens"] for r in sample)
    mean, widest = float(np.mean(gaps)), float(np.max(gaps))
    p995 = float(np.percentile(gaps, 99.5))
    numbers = {
        "mean_logit_gap": [mean, limits["mean_logit_gap"]],
        "p995_logit_gap": [p995, limits["p995_logit_gap"]],
        "widest_logit_gap": [widest, "-"],
        "served_tokens_checked": [int(len(gaps)), ">=1"],
        "tokens_in_vocab": [int(vocab_ok), 1],
        "lengths_as_asked": [int(length_ok), 1],
    }
    state_ok = True
    if held_state is not None:
        state_ok = held_state[0] >= held_state[1]
        numbers["recurrent_state_bytes"] = [int(held_state[0]), f">={held_state[1]}"]
    out = {"correct": bool(mean <= limits["mean_logit_gap"]
                           and p995 <= limits["p995_logit_gap"] and vocab_ok
                           and length_ok and state_ok and np.isfinite(widest)),
           "numbers": numbers}
    out["controls"] = {
        f"control_{c}_{stat}_logit_gap": float(f(g))
        for c, g in ctl.items()
        for stat, f in (("mean", np.mean), ("widest", np.max),
                        ("p995", lambda x: np.percentile(x, 99.5)))}
    return out
