"""A served cell of MiniCPM-SALA (block-sparse + lightning attention).

benchmark/serve_hybrid.py with another model, other weights, another
reference and SHARED PREFIXES: the engine is the same `LLMEngine` fed through
`submit()`; the recorder, the closed and open loops, the trace window with its
counter snapshots at both ends of the traced part, the sampling of finished
requests and the work between two instants are imported unchanged.  What
differs: set-up sends each shared document ONCE and alone (it ends on a page
boundary, so the engine hangs a state checkpoint on its last page and every
later request resumes there); `correct` is decided by
benchmark/reference/minicpm_sala_ref.py on the served tokens' logit gaps and
on numbers the gaps cannot see, held beside them: how many blocks a decode
query's selected list held against what the published rule gives for the
contexts served, and the float32 sizes of the recurrent state and of the
checkpoints.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import loadgen, serve
from benchmark import weights_minicpm_sala as W
from benchmark.serve_hybrid import _TraceWindow


def build_model(cfg, seed=None):
    """`MiniCPMSALAForCausalLM` at the config file's sizes, in its dtype,
    with the seeded weights when `seed` is given."""
    import paddle_tpu as paddle
    from paddle_tpu.models.minicpm_sala import (MiniCPMSALAConfig,
                                                MiniCPMSALAForCausalLM)
    from paddle_tpu.ops.sparse_attention import SparseSpec

    s = W.sizes(cfg)
    paddle.seed(0)
    mc = MiniCPMSALAConfig(
        vocab_size=s["vocab"], hidden_size=s["h"], intermediate_size=s["ffn"],
        num_hidden_layers=s["layers"], mixer_types=s["mixers"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        head_dim=s["head_dim"], lightning_nh=s["l_heads"],
        lightning_nkv=cfg["lightning_nkv"], lightning_head_dim=s["l_head_dim"],
        rope_theta=float(s["theta"]), rms_norm_eps=s["eps"],
        scale_emb=cfg["scale_emb"], scale_depth=cfg["scale_depth"],
        dim_model_base=cfg["dim_model_base"],
        residual_depth=cfg.get("published", cfg)["num_hidden_layers"],
        sparse=SparseSpec(**s["sparse"]), dtype=cfg["torch_dtype"])
    model = MiniCPMSALAForCausalLM(mc)
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


def _warm_up(eng, cfg, prefixes, seed, limit):
    """Set-up traffic: each shared document once, ALONE (its prefill ends on
    a page boundary and leaves a state checkpoint there), or two short
    requests where nothing is shared."""
    rng = np.random.default_rng([int(seed), 1])
    prompts = list(prefixes) or [
        rng.integers(0, cfg["vocab_size"], min(300, limit // 2), dtype=np.int32)
        for _ in range(2)]
    rec = serve._Recorder(eng, "w")
    for i, p in enumerate(prompts):
        rec.submit({"id": i, "prompt": p, "max_new_tokens": 4, "prefix": None}, None)
    if not rec.wait_all(time.perf_counter() + 600) \
            or any(r["error"] for r in rec.records):
        raise RuntimeError(f"set-up requests failed: {[r['error'] for r in rec.records]}")


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
    obs = {"kind": "serve_sala", "cfg": cfg, "traffic": traffic,
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
                        (before["stats"], obs["after"]["stats"]), ok))
    check["attempted"], check["failed"] = len(records), failed
    check["extra"] = {
        "drain_s": t_gave_up - t_close, "done_in_window": len(in_window),
        "queue_depth_at_close": after["stats"].get("queue_depth"),
        "active_slots_at_close": after["stats"].get("active_slots"),
        "ttft_p50_ms": serve._ms(serve.percentile(ttft, 0.5, failed, late)),
        "bytes_in_use_at_close": max(m.get("bytes_in_use", 0) for m in mem),
        "cache_kinds": stats["cache_kinds"],
        "recurrent_state": stats["recurrent_state"],
        "sparse_attention": stats["sparse_attention"],
        "prefix_cache": {k: v for k, v in (stats["prefix_cache"] or {}).items()
                         if isinstance(v, (int, float))},
    }
    check["extra"].update(check.pop("controls", {}))
    return e2e, obs, check


def state_bytes(cfg, slots):
    """Bytes of the lightning layers' state as the configuration states it
    (`assumed.precision`): float32 [heads, head_dim, head_dim] a layer, for
    `slots` sequences."""
    s = W.sizes(cfg)
    return slots * s["n_lightning"] * s["l_heads"] * s["l_head_dim"] ** 2 * 4


def blocks_read(sparse, n):
    """Blocks the published rule reads at context `n`, from the configuration
    file's `sparse_config` alone (no code of the program): every block up to
    `dense_len`, then `topk` with the forced ones counted inside it."""
    blocks = -(-int(n) // sparse["block_size"])
    return blocks if n <= sparse["dense_len"] else min(sparse["topk"], blocks)


def held_numbers(cfg, engine, stats, counted, served):
    """What the logit gaps cannot see, each as (reading, (low, high)):

    selected_blocks_per_query  blocks a decode query's selected list held, a
        layer call, over the counted part of the window (`counted`: the
        flattened stats at its two ends; the program counts them on the
        device FROM THE LIST the kernel reads: distinct blocks inside the
        context among its live entries), against `blocks_read` for the
        contexts served (their smallest and largest: equal, hence exact,
        where every context is past `dense_len` and holds `topk` blocks).
        It guards HOW MANY blocks are read and that the list is a sound
        one (no repeat, none off the context); WHICH blocks is seen by the
        gaps alone
    recurrent_state_bytes / state_checkpoint_bytes  at least the float32
        sizes the configuration states (a bfloat16 state moves no argmax)
    """
    sparse = W.sizes(cfg)["sparse"]
    sel, calls = (counted[1].get(k, 0) - counted[0].get(k, 0) for k in (
        "sparse_attention.decode.selected_blocks",
        "sparse_attention.decode.layer_calls"))
    rule = [blocks_read(sparse, n) for r in served if len(r["tokens"]) > 1
            for n in (len(r["prompt"]) + 1, len(r["prompt"]) + len(r["tokens"]))]
    rec = stats["recurrent_state"]
    ck = rec["checkpoints"] or {"bytes": 0, "capacity": 0}
    return {
        "selected_blocks_per_query": (sel / calls if calls else 0.0, (
            min(rule, default=0), max(rule, default=0))),
        "recurrent_state_bytes": (rec["bytes"], (
            state_bytes(cfg, engine["max_batch_slots"]), None)),
        "state_checkpoint_bytes": (ck["bytes"], (
            state_bytes(cfg, ck["capacity"]), None)),
    }


def compare(cfg, seed, sample, pad_to, limits, controls=None, held=None):
    """The numbers `correct` is decided on, each beside its limit.  The gaps
    are judged by their mean and their 99.5th percentile, as
    serve_hybrid.compare does and for its reason: with seeded weights the
    selection's 64th and 65th block lie close, so bfloat16 rounding flips a
    block at some token of most requests and the WIDEST gap of a sound run
    says nothing.  `controls`: names of benchmark/reference/minicpm_sala_ref's
    variants to read beside (a control run)."""
    from benchmark.reference import minicpm_sala_ref

    if not sample:
        return {"correct": False, "numbers": {"sampled_requests": [0, ">=1"]}}
    pairs = [(r["prompt"], r["tokens"]) for r in sample]
    if isinstance(controls, str):
        controls = [controls]
    gaps, ctl = minicpm_sala_ref.served_gap(cfg, seed, pairs, pad_to,
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
        held_ok &= got >= lo and (hi is None or got <= hi)
        numbers[name] = [got, f">={lo}" if hi is None else f"{lo}..{hi}"]
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
