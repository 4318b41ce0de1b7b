"""A served cell of a DeepSeek-V3-shaped decoder (multi-head latent attention
over one latent page pool a layer, gated routed experts with shared ones).

benchmark/serve_sala.py with another model, other weights and another
reference: the engine is the same `LLMEngine` fed through `submit()`; the
recorder, the closed and open loops, the trace window with its counter
snapshots at both ends of the traced part, the sampling of finished requests
and the work between two instants are imported unchanged.  Set-up sends each
shared document ONCE and alone (whole pages, so every later request maps
them through the prefix cache and prefills its own tokens only); `correct` is
decided by benchmark/reference/deepseek_v3_ref.py (the EXPANDED form, where
the program decodes absorbed) on the served tokens' logit gaps and on numbers
the gaps cannot see, held beside them: the latent pool's bytes against the
configuration's arithmetic, the context a decode query read against the
requests' own lengths, and the routed pairs a decode row.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import loadgen, serve
from benchmark import weights_deepseek_v3 as W
from benchmark.serve_hybrid import _TraceWindow
from benchmark.serve_sala import _warm_up


def build_model(cfg, seed=None):
    """`DeepseekV3ForCausalLM` at the config file's sizes, in its dtype, with
    the seeded weights when `seed` is given."""
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)

    s = W.sizes(cfg)
    paddle.seed(0)
    published = {k: cfg[k] for k in DeepseekV3Config.__dataclass_fields__
                 if k in cfg}
    mc = DeepseekV3Config(**published, experts_held=s["held"],
                          dtype=cfg["torch_dtype"])
    model = DeepseekV3ForCausalLM(mc)
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
    eng = LLMEngine(model, tracer=tracer, **job["engine"])
    return model, eng, tracer


def run(cell, seed, seconds, trace, clock0, log):
    """Returns (end_to_end metrics dict, obs for the readers, check numbers)."""
    import jax

    cfg, job, traffic = cell["config"], cell["job"], cell["traffic"]
    reqs, prefixes = loadgen.requests(traffic, cfg["vocab_size"], seed, seconds)
    limit = job["engine"]["max_seq_len"] - 1
    worst = max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs)
    if worst > limit:
        raise ValueError(f"traffic asks for {worst} tokens, engine holds {limit}")
    model, eng, tracer = build_engine(cfg, job, seed, len(reqs) + 64)
    log(f"engine built: {W.n_params(cfg) / 1e9:.2f} B parameters")
    log(f"warmup() took {eng.warmup():.1f} s")
    eng.start()
    try:
        _warm_up(eng, cfg, prefixes, seed, limit)
        log(f"{len(prefixes)} shared documents sent")
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
    obs = {"kind": "serve_latent", "cfg": cfg, "traffic": traffic,
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
                    job.get("control"), held=held_numbers(
                        cfg, job["engine"], stats,
                        (before["stats"], obs["after"]["stats"]),
                        obs["work"](*obs["window"])))
    check["attempted"], check["failed"] = len(records), failed
    check["extra"] = {
        "drain_s": t_gave_up - t_close, "done_in_window": len(in_window),
        "queue_depth_at_close": after["stats"].get("queue_depth"),
        "active_slots_at_close": after["stats"].get("active_slots"),
        "ttft_p50_ms": serve._ms(serve.percentile(ttft, 0.5, failed, late)),
        "bytes_in_use_at_close": max(m.get("bytes_in_use", 0) for m in mem),
        "cache_kinds": stats["cache_kinds"],
        "latent_attention": stats["latent_attention"],
        "moe": stats["moe"],
        "prefix_cache": {k: v for k, v in (stats["prefix_cache"] or {}).items()
                         if isinstance(v, (int, float))},
    }
    check["extra"].update(check.pop("controls", {}))
    return e2e, obs, check


def pool_bytes(cfg, engine):
    """Bytes of the latent pools as the configuration states them
    (`assumed.latent_cache`): kv_lora_rank + qk_rope_head_dim values a token a
    layer in the weights' dtype, for every page of the engine."""
    s = W.sizes(cfg)
    return engine["num_pages"] * engine["page_size"] * s["layers"] \
        * (s["latent"] + s["rope"]) * W.dtype_of(cfg).itemsize


#: a row may be padded to whole 128-lane tiles and no further
LANES = 128
#: how far the counted context may lie from the requests' own (the estimate
#: spreads a request's decode tokens evenly over its decode time)
CTX_BAND = 0.005


def held_numbers(cfg, engine, stats, counted, work):
    """What the logit gaps cannot see, each as (reading, (low, high)):

    latent_pool_bytes  stats()["cache_kinds"]["paged_latent"]["bytes"] between
        the configuration's arithmetic (`pool_bytes`) and the same rows padded
        to whole lanes: a pool of expanded keys and values would be 17.8 times
        the size, an fp8 one half
    latent_ctx_tokens_per_query  the context a decode query's kernel call was
        handed, a layer call, over the counted part of the window (`counted`:
        the flattened stats at its two ends), within `CTX_BAND` of the
        contexts served as `work` (serve.work_between: the requests' own
        lengths, no code of the program) gives them
    routed_pairs_per_row  the decode pairs the held experts served over the
        decode rows of the expert layers (the latent layers' calls a layer x
        expert layers): `num_experts_per_tok` exactly where every expert is
        held (no capacity, no dropped pair)
    """
    s = W.sizes(cfg)
    d = lambda k: counted[1].get(k, 0) - counted[0].get(k, 0)  # noqa: E731
    ctx, calls, pairs = (d(k) for k in (
        "latent_attention.decode.context_tokens",
        "latent_attention.decode.layer_calls", "moe.decode.pairs_held"))
    low = pool_bytes(cfg, engine)
    row = s["latent"] + s["rope"]
    own = work["decode_ctx_sum"] / work["decode_tokens"] \
        if work["decode_tokens"] else 0.0
    rows = calls / s["layers"] * s["kinds"].count("E")
    share = s["n_held"] / s["router"]
    return {
        "latent_pool_bytes": (stats["cache_kinds"]["paged_latent"]["bytes"], (
            low, low // row * (-(-row // LANES) * LANES))),
        "latent_ctx_tokens_per_query": (ctx / calls if calls else 0.0, (
            own * (1 - CTX_BAND), own * (1 + CTX_BAND))),
        "routed_pairs_per_row": (pairs / rows if rows else 0.0, (
            s["top_k"] if share == 1 else 0, s["top_k"])),
    }


def compare(cfg, seed, sample, pad_to, limits, controls=None, held=None):
    """The numbers `correct` is decided on, each beside its limit.  The gaps
    are judged by their mean and their 99.5th percentile, as
    serve_hybrid.compare does and for its reason: with seeded weights a
    router's sixth and seventh score lie close, so bfloat16 rounding flips an
    expert at some token of most requests and the WIDEST gap of a sound run
    says nothing.  `controls`: names of benchmark/reference/deepseek_v3_ref's
    variants to read beside (a control run)."""
    from benchmark.reference import deepseek_v3_ref

    if not sample:
        return {"correct": False, "numbers": {"sampled_requests": [0, ">=1"]}}
    pairs = [(r["prompt"], r["tokens"]) for r in sample]
    if isinstance(controls, str):
        controls = [controls]
    gaps, ctl = deepseek_v3_ref.served_gap(cfg, seed, pairs, pad_to,
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
    held_ok = True
    for name, (got, (lo, hi)) in (held or {}).items():
        held_ok &= lo <= got <= hi
        numbers[name] = [got, f"{lo}..{hi}"]
    out = {"correct": bool(mean <= limits["mean_logit_gap"]
                           and p995 <= limits["p995_logit_gap"] and vocab_ok
                           and length_ok and held_ok and np.isfinite(widest)),
           "numbers": numbers}
    out["controls"] = {
        f"control_{q}_{stat}_logit_gap": float(f(g))
        for q, g in ctl.items()
        for stat, f in (("mean", np.mean), ("widest", np.max),
                        ("p995", lambda x: np.percentile(x, 99.5)))}
    return out
