"""A served cell of a hybrid decoder (Nemotron-H: Mamba-2, experts, attention).

benchmark/serve.py with another model, other weights and another reference:
the engine is the same `LLMEngine` fed through `submit()`, and the recorder,
the closed and open loops, the trace window, the sampling of finished
requests and the work between two instants are imported from it unchanged.
What differs: the model is built in its own dtype and handed
benchmark/weights_nemotron_h.py's leaves; `correct` is decided by
benchmark/reference/nemotron_h_ref.py; and a traced run also snapshots the
counters at both ends of the TRACED part (`obs["traced_counters"]`), so the
kernels' work functions count the pairs and tokens of exactly those seconds.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import loadgen, serve
from benchmark import weights_nemotron_h as W

#: what a control run's comparison adds, copied into the line's `extra`
CONTROL_KEYS = ("control_mean_logit_gap", "control_p995_logit_gap",
                "control_widest_logit_gap")


def build_model(cfg, seed=None):
    """`NemotronHForCausalLM` at the config file's sizes, in its dtype, with
    the seeded weights when `seed` is given."""
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM

    s = W.sizes(cfg)
    paddle.seed(0)
    nc = NemotronHConfig(
        vocab_size=s["vocab"], hidden_size=s["h"], num_hidden_layers=s["layers"],
        hybrid_override_pattern=s["pattern"], num_attention_heads=s["heads"],
        num_key_value_heads=s["kv_heads"], head_dim=s["head_dim"],
        mamba_num_heads=s["m_heads"], mamba_head_dim=s["m_head_dim"],
        n_groups=s["groups"], ssm_state_size=s["state"], conv_kernel=s["conv_k"],
        chunk_size=s["chunk"], n_routed_experts=s["router"],
        num_experts_per_tok=s["top_k"], moe_intermediate_size=s["ffn"],
        moe_shared_expert_intermediate_size=s["shared_ffn"],
        routed_scaling_factor=s["scaling"], norm_topk_prob=cfg["norm_topk_prob"],
        layer_norm_epsilon=s["eps"], experts_held=s["held"],
        dtype=cfg["torch_dtype"])
    model = NemotronHForCausalLM(nc)
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


class _TraceWindow(serve._TraceWindow):
    """serve's window, with the counters read again where the traced part
    begins and ends (`edges`)."""

    def _run(self, t0):
        import jax

        try:
            time.sleep(max(0.0, t0 + self.offset - time.perf_counter()))
            self.snapshot = self.snapshot_fn()
            self.split = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                first = self.snapshot_fn()
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench_trace_window"):
                    time.sleep(self.length)
                self.bounds = (a, time.perf_counter())
                self.edges = (first, self.snapshot_fn())
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported by finish()
            self._error = e


def _warm_up(eng, cfg, seed, limit):
    """Two short requests, so the pump has run every path it will."""
    rng = np.random.default_rng([int(seed), 1])
    rec = serve._Recorder(eng, "w")
    for i in range(2):
        prompt = rng.integers(0, cfg["vocab_size"], min(300, limit // 2), dtype=np.int32)
        rec.submit({"id": i, "prompt": prompt, "max_new_tokens": 4, "prefix": None}, None)
    if not rec.wait_all(time.perf_counter() + 300) \
            or any(r["error"] for r in rec.records):
        raise RuntimeError(f"set-up requests failed: {[r['error'] for r in rec.records]}")


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
    log(f"engine built: {W.n_params(cfg) / 1e9:.2f} B parameters")
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
    obs = {"kind": "serve_hybrid", "cfg": cfg, "traffic": traffic,
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
    }
    for k in CONTROL_KEYS:
        if k in check:  # only a control run has them
            check["extra"][k] = check[k]
    return e2e, obs, check


def state_bytes(cfg, slots):
    """Bytes of the Mamba-2 layers' per-slot state as the configuration
    states it (`assumed.ssm_state`): the SSM state in float32, the
    convolution's last inputs in the weights' dtype."""
    s = W.sizes(cfg)
    a_slot = s["m_heads"] * s["m_head_dim"] * s["state"] * 4 \
        + (s["conv_k"] - 1) * s["conv_c"] * W.dtype_of(cfg).itemsize
    return slots * s["pattern"].count("M") * a_slot


def compare(cfg, seed, sample, pad_to, limits, quant=None, held_state=None):
    """The numbers `correct` is decided on, each beside its limit.
    `held_state` is (bytes of recurrent state the engine reported, bytes the
    configuration states)."""
    from benchmark.reference import nemotron_h_ref

    if not sample:
        return {"correct": False, "numbers": {"sampled_requests": [0, ">=1"]}}
    pairs = [(r["prompt"], r["tokens"]) for r in sample]
    gaps, control = nemotron_h_ref.served_gap(cfg, seed, pairs, pad_to, quant)
    vocab_ok = all(0 <= int(t) < cfg["vocab_size"] for _, o in pairs for t in o)
    length_ok = all(len(r["tokens"]) == r["max_new_tokens"] for r in sample)
    # Two numbers of the gaps decide, each between a sound run's and the fp8
    # control's reading (the workload file has both).  With seeded weights the
    # router's scores lie close together, so bfloat16 rounding flips a token's
    # sixth expert in some layer often enough that the WIDEST gap of a sound
    # run reaches the control's: it is printed with no limit.  The MEAN sees
    # a fault spread over the tokens; the 99.5th PERCENTILE sees one that
    # strikes a token in 128 or more and leaves the mean where it was.
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
        # the gaps cannot see the SSM state's precision (a bfloat16 state
        # moves no argmax), so its size is held to the configuration's
        state_ok = held_state[0] >= held_state[1]
        numbers["recurrent_state_bytes"] = [int(held_state[0]), f">={held_state[1]}"]
    out = {"correct": bool(mean <= limits["mean_logit_gap"]
                           and p995 <= limits["p995_logit_gap"] and vocab_ok
                           and length_ok and state_ok and np.isfinite(widest)),
           "numbers": numbers}
    if control is not None:
        out["control_widest_logit_gap"] = float(np.max(control))
        out["control_p995_logit_gap"] = float(np.percentile(control, 99.5))
        out["control_mean_logit_gap"] = float(np.mean(control))
    return out
