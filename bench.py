"""Headline benchmarks (BASELINE.md north stars).

1. LLaMA decoder pretrain step — tokens/sec/chip + MFU (BASELINE config #5 /
   ERNIE north star: >=70% MFU target on v5e, peak 197 TFLOP/s bf16).
2. ResNet-50 training throughput — images/s + MFU (BASELINE config #2).

Runs the compiled TrainStep (forward+backward+optimizer in one XLA program) in
bfloat16 on whatever accelerator is attached (the driver provides one TPU v5e chip)
and prints ONE JSON line.  The primary metric is the transformer MFU; ResNet numbers
ride along as extra fields.

vs_baseline: MFU / 0.70 (the BASELINE.md target); >1.0 beats the target.
"""
from __future__ import annotations

import json
import time

import numpy as np

V5E_PEAK_FLOPS = 197e12  # bf16, one v5e chip (nominal)


def paged_capacity_trace(L_pad, page_size=128):
    """Deterministic mixed-length serving trace for the paged-kv capacity
    accounting (shared with tools/project_pod.py so the 'derived' PROJECTION
    numbers can never drift from what bench.py measures): context lengths
    100..L_pad in steps of 100 — deliberately OFF the page grid so the
    round-up-to-page waste is represented.  Returns (trace, mean pages per
    request at `page_size`)."""
    trace = list(range(100, int(L_pad) + 1, 100))
    pages_mean = sum(-(-t // page_size) for t in trace) / len(trace)
    return trace, pages_mean


def shared_prefix_trace(L_pad, page_size=128, n_requests=32):
    """Deterministic fleet-style SHARED-PREFIX serving trace (shared with
    tools/project_pod.py so the 'derived' PROJECTION numbers can never
    drift from what bench.py measures): every request carries one common
    system prompt plus a small varied tail.  The shared length is
    deliberately OFF the page grid so the tail page is partially filled —
    later requests fork it copy-on-write, the behavior the prefix cache
    must pay for.  Returns the trace geometry plus the analytic per-request
    page accounting: admission charges only the UNIQUE pages (tail + the
    COW fork), so effective capacity multiplies by
    total_pages / unique_pages as the fleet share amortizes."""
    ps = int(page_size)
    # the shared prompt spans N full pages PLUS ps/8 tokens into the next
    # page, and the varied tail + decode stay inside that same page — so
    # the divergence point always sits inside a partially-filled shared
    # page; N is clamped so the whole trace fits inside L_pad
    tail_len = max(1, ps // 16)
    new_tokens = max(1, ps // 8)
    extra = max(1, ps // 8) + tail_len + new_tokens
    if int(L_pad) - extra < ps:
        raise ValueError(
            f"shared_prefix_trace needs L_pad >= page_size + {extra} to fit "
            f"one full shared page plus the divergent tail; got "
            f"L_pad={L_pad}, page_size={ps}")
    shared_full_pages = max(1, min((3 * int(L_pad)) // 4 // ps,
                                   (int(L_pad) - extra) // ps))
    shared_len = shared_full_pages * ps + max(1, ps // 8)
    total_tokens = shared_len + tail_len + new_tokens
    total_pages = -(-total_tokens // ps)
    unique_pages = total_pages - shared_full_pages
    # every request but the first serves its shared tokens from the cache
    hit_ratio = (n_requests - 1) / n_requests \
        * shared_len / (shared_len + tail_len)
    return {"n_requests": n_requests, "shared_len": shared_len,
            "tail_len": tail_len, "new_tokens": new_tokens,
            "total_pages": total_pages,
            "shared_full_pages": shared_full_pages,
            "unique_pages": unique_pages,
            "hit_ratio": round(hit_ratio, 4)}


def _measure_gemm_peak():
    """Measured bf16 gemm ceiling of the attached chip (TF/s): a 30-deep
    in-jit chain of [8192,8192]x[8192,8192] matmuls.  Context for the MFU
    number: mfu_vs_measured shows how close the compiled step is to what a
    plain matmul chain reaches on the same chip."""
    import time

    import jax
    import jax.numpy as jnp

    n, iters = 8192, 30
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, n) * 0.01, jnp.bfloat16)
    w = jnp.asarray(rng.randn(n, n) * 0.01, jnp.bfloat16)

    @jax.jit
    def chain(x, w):
        # no per-iter renorm: values decay to zero but MXU timing is
        # magnitude-independent, and any elementwise op would tax the
        # measurement with extra HBM passes
        def body(c, _):
            return c @ w, ()
        return jax.lax.scan(body, x, None, length=iters)[0]

    r = chain(x, w)
    float(jnp.sum(r[:1, :1].astype(jnp.float32)))
    ws = []
    for _ in range(5):
        t0 = time.perf_counter()
        r = chain(x, w)
        float(jnp.sum(r[:1, :1].astype(jnp.float32)))
        ws.append(time.perf_counter() - t0)
    # median window, each ended by a real sync (the scalar fetch above)
    dt = sorted(ws)[len(ws) // 2]
    return 2 * n * n * n * iters / dt / 1e12


def _measure_conv_peak():
    """Measured bf16 conv ceiling (TF/s) over the ResNet-50 residual-stage
    3x3 shapes (56²x64, 28²x128, 14²x256, 7²x512 — equal FLOPs per stage by
    design), each a pure same-channel conv chain with NO elementwise
    traffic, so the number is an upper bound the train step's effective
    TF/s can be read against (it cannot sit below a well-formed model's
    achieved rate the way a single narrow-channel probe did)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax import lax

    # iters sized so each WINDOW is ~100+ ms, far above dispatch + sync
    # cost; median window, not best, since this is a denominator for the
    # ResNet MFU story
    B, iters = 128, 600
    rng = np.random.RandomState(0)
    total_flops = 0.0
    total_dt = 0.0
    for H, C in ((56, 64), (28, 128), (14, 256), (7, 512)):
        x = jnp.asarray(rng.randn(B, C, H, H) * 0.1, jnp.bfloat16)
        w = jnp.asarray(rng.randn(C, C, 3, 3) * 0.1, jnp.bfloat16)
        dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))

        @jax.jit
        def chain(x, w, dn=dn):
            def body(c, _):
                return lax.conv_general_dilated(
                    c, w, (1, 1), "SAME", dimension_numbers=dn), ()
            return jax.lax.scan(body, x, None, length=iters)[0]

        r = chain(x, w)
        float(jnp.sum(r[:1, :1, :1, :1].astype(jnp.float32)))
        ws = []
        for _ in range(3):
            t0 = time.perf_counter()
            r = chain(x, w)
            float(jnp.sum(r[:1, :1, :1, :1].astype(jnp.float32)))
            ws.append(time.perf_counter() - t0)
        total_flops += 2 * B * H * H * C * C * 9 * iters
        total_dt += sorted(ws)[1]
    return total_flops / total_dt / 1e12


def _measure_hbm_bw():
    """Measured streaming READ bandwidth (GB/s) — the decode denominator
    (decode streams weights+kv and writes almost nothing).

    Probe design notes (each clause closes a measured failure mode):
    - per-pass `sum(|x + c|, axis=1)` with a carried c: not algebraically
      factorable, so XLA can neither hoist the reduction out of the loop
      (sum(x)+n*c) nor push it into the operand (reduce-max probes both
      collapsed to tiny loops and read >1 TB/s);
    - 200 chained passes over 512 MB = a ~150 ms window, so the one host
      sync that ends it is noise;
    - median-of-5 windows, not best: this number is a denominator, so an
      optimistic outlier would overstate every roofline fraction built on it."""
    import time

    import jax
    import jax.numpy as jnp

    R, C, iters = 16384, 16384, 200  # 512 MB bf16, ~77 GB read per window
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(R, C) * 0.1, jnp.bfloat16)

    @jax.jit
    def chain(x):
        def body(c, _):
            m = jnp.sum(jnp.abs(x + c[:, None]), axis=1, dtype=jnp.float32)
            return (m * jnp.float32(1e-6)).astype(jnp.bfloat16), ()
        return jax.lax.scan(body, jnp.zeros((R,), jnp.bfloat16), None,
                            length=iters)[0]

    r = chain(x)
    float(jnp.sum(r[:2].astype(jnp.float32)))
    windows = []
    for _ in range(5):
        t0 = time.perf_counter()
        r = chain(x)
        float(jnp.sum(r[:2].astype(jnp.float32)))
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[2]
    return 2 * R * C * iters / dt / 1e9


def _bench_llama(on_accel):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_accel:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=12, num_attention_heads=16, num_key_value_heads=16,
            max_position_embeddings=2048, dtype="bfloat16",
            tensor_parallel=False, use_flash_attention=True,
        )
        batch, seq, steps, warmup = 8, 2048, 10, 3
    else:
        cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False)
        batch, seq, steps, warmup = 2, 128, 2, 1

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                 parameters=model.parameters())

    def loss_fn(ids, labels):
        logits = model(ids)
        # no f32 cast: cross_entropy's fused hard-label path does the
        # softmax math in f32 WITHOUT materializing f32 [N, 32000] logits
        # (2.1 GB/pass at this shape)
        return paddle.nn.functional.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]),
            labels.reshape([-1]),
        )

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    ids = paddle.to_tensor(np.random.randint(0, cfg.vocab_size, (batch, seq), np.int32))
    labels = paddle.to_tensor(np.random.randint(0, cfg.vocab_size, (batch, seq), np.int32))

    for _ in range(warmup):
        loss = step(ids, labels)
    float(loss.item())
    # median of three measurement windows: robust to remote-link hiccups
    # without silently reporting a lucky fastest window
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids, labels)
        float(loss.item())
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[1]  # median window; loss.item() is the sync

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tokens = batch * seq
    # model flops per train step: 6*N per token (fwd 2N + bwd 4N)
    # + causal attention matmuls: fwd 2*2*B*S^2*h per layer (QK^T, AV; causal => /2), x3 train
    attn_flops = 3 * 2 * batch * seq * seq * cfg.hidden_size * cfg.num_hidden_layers
    flops_per_step = 6 * n_params * tokens + attn_flops
    tps = tokens * steps / dt
    mfu = (flops_per_step * steps / dt) / V5E_PEAK_FLOPS
    return {"llama_tokens_per_sec_per_chip": round(tps, 1),
            "llama_mfu": round(mfu, 4),
            "llama_n_params": n_params,
            "llama_step_ms": round(1000 * dt / steps, 1)}


def _bench_decode(on_accel):
    """Autoregressive decode throughput: compiled static-cache generate()
    (prefill + lax.scan over steps in ONE program)."""
    import time

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_accel:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=12, num_attention_heads=16, num_key_value_heads=16,
            max_position_embeddings=2048, dtype="bfloat16",
            tensor_parallel=False, use_flash_attention=True,  # flash prefill
        )
        batch, prompt_len, new_tokens = 8, 1024, 128
    else:
        cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False)
        batch, prompt_len, new_tokens = 2, 16, 8

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    model.eval()
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, prompt_len), np.int32))

    def timed(the_ids, ntok, cache_dtype=None, kv_layout=None, reps=3):
        out = model.generate(the_ids, max_new_tokens=ntok,
                             cache_dtype=cache_dtype,
                             kv_layout=kv_layout)  # compile
        _ = np.asarray(out._value)
        ws = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = model.generate(the_ids, max_new_tokens=ntok,
                                 cache_dtype=cache_dtype,
                                 kv_layout=kv_layout)
            _ = np.asarray(out._value)
            ws.append(time.perf_counter() - t0)
        # median window: a best-of window would overstate the achieved rate
        return sorted(ws)[len(ws) // 2]

    def steady(the_ids, ntok, cache_dtype=None, kv_layout=None):
        d_full = timed(the_ids, ntok, cache_dtype, kv_layout)
        d_half = timed(the_ids, ntok // 2, cache_dtype, kv_layout)
        return d_full, (d_full - d_half) / (ntok - ntok // 2)

    dt, per_tok = steady(ids, new_tokens) if on_accel else (
        timed(ids, new_tokens), 0.0)
    res = {"llama_decode_tokens_per_sec": round(batch * new_tokens / dt, 1),
           "llama_decode_batch": batch, "llama_decode_prompt_len": prompt_len}
    if on_accel:
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        # the static cache pads L to a multiple of 128 for the Pallas decode
        # kernel; the step streams the PADDED buffers (generation.py L_pad)
        L_pad = ((prompt_len + new_tokens + 127) // 128) * 128
        hd = cfg.hidden_size // cfg.num_attention_heads
        # kv_elems counts BOTH k and v rows (the leading factor 2), so the
        # per-row cost below is payload + ONE f32 scale
        kv_elems = 2 * cfg.num_hidden_layers * batch * L_pad \
            * cfg.num_key_value_heads
        kv_bytes_bf16 = kv_elems * hd * 2
        kv_bytes_int8 = kv_elems * (hd * 1 + 4)  # int8 payload + f32 scale
        # streamed params exclude the INPUT embedding table: decode gathers B
        # rows of it, it never streams (the r4 floor counted it and the round-5
        # kernel then beat that "floor" — the accounting was the error)
        streamed = n_params - cfg.vocab_size * cfg.hidden_size
        res["llama_decode_stream_gb_per_tok"] = round(
            (2 * streamed + kv_bytes_bf16) / 1e9, 3)
        if per_tok > 1e-6:
            res["llama_decode_ms_per_token"] = round(per_tok * 1000, 2)
            res["llama_decode_steady_tokens_per_sec"] = round(batch / per_tok, 1)
        # int8 cache: the Pallas decode kernel dequantizes in VMEM, so the
        # int8 stream is genuinely half — capacity AND bandwidth lever
        _, per_q8 = steady(ids, new_tokens, "int8")
        if per_q8 > 1e-6:
            res["llama_decode_int8_ms_per_token"] = round(per_q8 * 1000, 2)
            res["llama_decode_int8_steady_tokens_per_sec"] = round(
                batch / per_q8, 1)
        res["llama_decode_int8_stream_gb_per_tok"] = round(
            (2 * streamed + kv_bytes_int8) / 1e9, 3)
        # int8 capacity win: max decode batch at this context before the kv
        # cache exhausts HBM (measured device limit when the runtime reports
        # one), bf16 vs int8 — the judge-requested kv_int8_max_batch_gain
        try:
            import jax as _jax

            stats = _jax.devices()[0].memory_stats() or {}
            hbm = float(stats.get("bytes_limit", 16e9))
        except Exception:
            hbm = 16e9
        budget = hbm * 0.9 - 2 * n_params  # 10% runtime/activation slack
        per_batch_bf16 = kv_bytes_bf16 / batch
        per_batch_int8 = kv_bytes_int8 / batch
        res["kv_int8_max_batch_gain"] = round(
            (budget / per_batch_int8) / max(budget / per_batch_bf16, 1e-9), 2)
        res["kv_bf16_max_batch"] = int(budget / per_batch_bf16)
        res["kv_int8_max_batch"] = int(budget / per_batch_int8)
        # throughput scaling: weights amortize over a bigger decode batch
        ids32 = paddle.to_tensor(
            np.random.randint(0, cfg.vocab_size, (32, prompt_len), np.int32))
        _, per32 = steady(ids32, new_tokens)
        if per32 > 1e-6:
            res["llama_decode_b32_steady_tokens_per_sec"] = round(32 / per32, 1)
        # int8 at the capacity-bound batch: its halved kv stream must win here
        _, per32q = steady(ids32, new_tokens, "int8")
        if per32q > 1e-6:
            res["llama_decode_int8_b32_steady_tokens_per_sec"] = round(
                32 / per32q, 1)
        # PAGED decode (ragged paged attention kernel behind page tables):
        # same math, page-pool residency — the serving engine's layout
        _, per_pg = steady(ids, new_tokens, kv_layout="paged")
        if per_pg > 1e-6:
            res["llama_decode_paged_ms_per_token"] = round(per_pg * 1000, 2)
            res["llama_decode_paged_steady_tokens_per_sec"] = round(
                batch / per_pg, 1)
        _, per_pg8 = steady(ids, new_tokens, "int8", kv_layout="paged")
        if per_pg8 > 1e-6:
            res["llama_decode_paged_int8_steady_tokens_per_sec"] = round(
                batch / per_pg8, 1)
        _, per_pg32 = steady(ids32, new_tokens, kv_layout="paged")
        if per_pg32 > 1e-6:
            res["llama_decode_paged_b32_steady_tokens_per_sec"] = round(
                32 / per_pg32, 1)
        # paged CAPACITY: a dense server reserves L_pad rows per slot (the
        # longest admissible context); pages follow ACTUAL lengths.  Model
        # the deterministic mixed-length trace from paged_capacity_trace
        # (contexts 100..L_pad step 100, page_size 128) and report the max
        # decode batch the same HBM budget holds (the dense counterpart of
        # this accounting is kv_bf16_max_batch above, whose every slot costs
        # the full L_pad rows)
        ps_pg = 128
        trace, pages_mean = paged_capacity_trace(L_pad, ps_pg)
        rows_mean = pages_mean * ps_pg
        row_bytes_bf16 = 2 * cfg.num_hidden_layers \
            * cfg.num_key_value_heads * hd * 2
        row_bytes_int8 = 2 * cfg.num_hidden_layers \
            * cfg.num_key_value_heads * (hd + 4)
        res["kv_paged_max_batch"] = int(budget / (rows_mean * row_bytes_bf16))
        res["kv_paged_int8_max_batch"] = int(
            budget / (rows_mean * row_bytes_int8))
        res["kv_paged_max_batch_gain"] = round(
            res["kv_paged_max_batch"] / max(res["kv_bf16_max_batch"], 1), 2)
        # fraction of allocated page rows holding real tokens on this trace
        res["kv_paged_pool_utilization"] = round(
            sum(trace) / (len(trace) * rows_mean), 3)
        # PREFIX-CACHE capacity: the shared-prefix fleet trace (one system
        # prompt + varied tails).  Admission charges only UNIQUE pages, so
        # the same budget holds (budget_pages - shared) / unique_per_req
        # concurrent requests — vs budget_pages / total_pages unshared
        tr = shared_prefix_trace(L_pad, ps_pg)
        page_bytes_bf16 = ps_pg * row_bytes_bf16
        page_bytes_int8 = ps_pg * row_bytes_int8
        for tag, pb in (("", page_bytes_bf16), ("int8_", page_bytes_int8)):
            budget_pages = budget / pb
            res[f"kv_prefix_{tag}max_batch"] = int(
                (budget_pages - tr["shared_full_pages"])
                // tr["unique_pages"])
        res["kv_prefix_max_batch_gain"] = round(
            res["kv_prefix_max_batch"] / max(res["kv_paged_max_batch"], 1),
            2)
        res["kv_prefix_trace_hit_ratio"] = tr["hit_ratio"]
        res["kv_prefix_trace"] = {k: tr[k] for k in
                                  ("shared_len", "tail_len", "new_tokens",
                                   "total_pages", "unique_pages")}
    return res


def _bench_prefix_cache(on_accel):
    """Shared-prefix serving trace through the REAL engine (prefix cache
    on): measures the achieved llm_prefix_cache_hit_ratio, COW forks and
    prefix evictions on the deterministic trace shared_prefix_trace
    describes — the measured side of the kv_prefix_max_batch accounting
    above.  Runs a scaled-down trace on CPU so the number exists (tiny) in
    every round."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_accel:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", tensor_parallel=False,
            use_flash_attention=True)
        L, ps, slots, n_req, new_toks = 1152, 128, 8, 16, 16
    else:
        cfg = LlamaConfig.tiny(tensor_parallel=False,
                               use_flash_attention=False)
        L, ps, slots, n_req, new_toks = 128, 32, 2, 6, 4

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    model.eval()
    tr = shared_prefix_trace(L, ps, n_requests=n_req)
    rng = np.random.RandomState(0)
    shared = rng.randint(0, cfg.vocab_size, tr["shared_len"]).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.randint(0, cfg.vocab_size, tr["tail_len"])
                               .astype(np.int32)]) for _ in range(n_req)]
    eng = LLMEngine(model, max_batch_slots=slots, max_seq_len=L,
                    kv_layout="paged", page_size=ps,
                    num_pages=slots * (tr["total_pages"] + 1),
                    prefill_chunk=ps)
    eng.warmup()
    t0 = time.perf_counter()
    futs = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
    eng.run_until_complete()
    dt = max(time.perf_counter() - t0, 1e-6)
    for f in futs:
        f.result(timeout=1)
    # engine-local counts, not the process-global registry's
    st = eng.stats()["prefix_cache"]
    return {"llm_prefix_cache_hit_ratio": round(st["hit_ratio"], 4),
            "prefix_trace_requests": n_req,
            "prefix_cow_copies": int(st["cow_copies"]),
            "prefix_evictions": int(st["evictions"]),
            "prefix_trace_tokens_per_sec": round(
                n_req * new_toks / dt, 1)}


def _bench_kv_tiers(on_accel):
    """Hierarchical kv tiers through the REAL engine: the effective prefix
    capacity multiplier over HBM-only, the promote-vs-reprefill cost per
    page (the economics that justify the copy), and the off-tick-path
    guard number ``kv_promote_us_per_page`` — the per-page promotion
    latency the CI sentinel watches so a regression that drags the upload
    toward re-prefill cost fails loudly instead of silently burning the
    capacity win."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_accel:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", tensor_parallel=False,
            use_flash_attention=True)
        L, ps, slots, host_pages, plen, new_toks = 1152, 128, 8, 64, 640, 8
    else:
        cfg = LlamaConfig.tiny(tensor_parallel=False,
                               use_flash_attention=False)
        L, ps, slots, host_pages, plen, new_toks = 128, 32, 2, 16, 96, 2

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    num_pages = slots * (plen // ps + 2)
    eng = LLMEngine(model, max_batch_slots=slots, max_seq_len=L,
                    kv_layout="paged", page_size=ps, num_pages=num_pages,
                    prefill_chunk=ps, host_cache_pages=host_pages)
    eng.warmup()
    # per-call promotion timing, engine-local (the registry histogram
    # aggregates across every engine the process ever ran)
    promote = {"s": 0.0, "pages": 0}
    inner = eng._promote_from_tiers

    def timed(req):
        t = time.perf_counter()
        n = inner(req)
        promote["s"] += time.perf_counter() - t
        promote["pages"] += n
        return n

    eng._promote_from_tiers = timed
    # warm the gather/upload programs on a same-shape cycle (same pow-2
    # upload bucket): first use compiles, and a compile is not the number
    warm = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
    eng.generate(warm, max_new_tokens=1)
    while eng.demote_step(force=True):
        pass
    eng._evict_prefix(int(eng._page_cached.sum()))
    eng.generate(warm, max_new_tokens=1)
    promote["s"], promote["pages"] = 0.0, 0
    tiers0 = eng.stats()["prefix_cache"]["tiers"]
    prompt = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
    t0 = time.perf_counter()
    eng.generate(prompt, max_new_tokens=new_toks)   # cold chunked prefill
    t_cold = time.perf_counter() - t0
    while eng.demote_step(force=True):              # stage every page ...
        pass
    eng._evict_prefix(int(eng._page_cached.sum()))  # ... and drop the HBM copy
    t0 = time.perf_counter()
    eng.generate(prompt, max_new_tokens=new_toks)   # promote path
    t_promote = time.perf_counter() - t0
    fresh = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
    t0 = time.perf_counter()
    eng.generate(fresh, max_new_tokens=new_toks)    # warm re-prefill baseline
    t_reprefill = time.perf_counter() - t0
    pages = max(promote["pages"], 1)
    tiers = eng.stats()["prefix_cache"]["tiers"]
    # capacity: pages a warm prefix can live in without being destroyed —
    # HBM page pool (minus the trash page) alone vs with the lower tiers
    hbm_pages = num_pages - 1
    return {
        "kv_tier_capacity_multiplier": round(
            (hbm_pages + host_pages) / hbm_pages, 2),
        "kv_tier_host_pages": host_pages,
        "kv_tier_hbm_pages": hbm_pages,
        "kv_promote_us_per_page": round(1e6 * promote["s"] / pages, 1),
        "kv_promote_vs_reprefill_ratio": round(
            t_promote / max(t_reprefill, 1e-9), 3),
        "kv_tier_promoted_pages": int(tiers["promotions"]
                                      - tiers0["promotions"]),
        "kv_tier_demoted_pages": int(tiers["demotions"]
                                     - tiers0["demotions"]),
        "kv_tier_hit_tokens": int(tiers["host"]["hit_tokens"]
                                  + tiers["disk"]["hit_tokens"]
                                  - tiers0["host"]["hit_tokens"]
                                  - tiers0["disk"]["hit_tokens"]),
        "kv_tier_cold_prefill_ms": round(t_cold * 1e3, 1),
        "kv_tier_promote_path_ms": round(t_promote * 1e3, 1),
    }


def _bench_spec_decode(on_accel):
    """Speculative decoding through the REAL engine: steady decode tok/s
    spec-on vs spec-off on the same deterministic trace, plus the
    acceptance/rollback accounting behind the speedup.

    The drafter is a REPLAY drafter (each request's precomputed solo
    greedy continuation) — deterministic and model-independent, so the
    number isolates the verify-path mechanics (K+1 tokens per compiled
    call, rollback trims) at a controlled acceptance rate rather than
    mixing in a particular corpus's n-gram hit rate.  The engine-reported
    acceptance_ratio and rollback counters are emitted alongside so a
    regression in EITHER the mechanism or the accounting moves a number."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_accel:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", tensor_parallel=False,
            use_flash_attention=True)
        slots, L, ps, plen, new_toks, K = 8, 1024, 128, 256, 64, 4
    else:
        cfg = LlamaConfig.tiny(tensor_parallel=False,
                               use_flash_attention=False)
        slots, L, ps, plen, new_toks, K = 2, 128, 32, 16, 8, 3

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_accel:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
               for _ in range(slots)]
    ids = paddle.to_tensor(np.stack(prompts))
    solo = np.asarray(model.generate(ids, max_new_tokens=new_toks)._value)
    seqs = [np.concatenate([p, solo[i]]) for i, p in enumerate(prompts)]

    class _Replay:
        name = "replay"

        def propose(self, context, k):
            ctx = np.asarray(context, np.int32).reshape(-1)
            out = np.zeros(int(k), np.int32)
            for s in seqs:
                if ctx.size <= s.size and (s[:plen] == ctx[:plen]).all():
                    tail = s[ctx.size:ctx.size + int(k)]
                    out[:tail.size] = tail
                    break
            return out

    def run(spec_k, drafter=None):
        eng = LLMEngine(model, max_batch_slots=slots, max_seq_len=L,
                        kv_layout="paged", page_size=ps, prefill_chunk=ps,
                        spec_k=spec_k, spec_draft=drafter)
        eng.warmup()
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
        eng.run_until_complete()
        dt = max(time.perf_counter() - t0, 1e-6)
        for f in futs:
            f.result(timeout=1)  # parity itself is the test suite's job
        return slots * new_toks / dt, eng.stats()["spec"]

    off_tps, _ = run(0)
    on_tps, spec = run(K, _Replay())
    return {
        "spec_decode_tokens_per_sec": round(on_tps, 1),
        "spec_off_tokens_per_sec": round(off_tps, 1),
        "spec_decode_speedup": round(on_tps / max(off_tps, 1e-6), 2),
        "spec_decode_batch": slots,
        "spec_k": K,
        "spec_acceptance_ratio": round(spec["acceptance_ratio"], 4),
        "spec_verify_calls": int(spec["verify_calls"]),
        "spec_rolled_back_tokens": int(spec["rolled_back_tokens"]),
        "spec_rolled_back_pages": int(spec["rolled_back_pages"]),
    }


def _bench_ragged_attention(on_accel):
    """ONE ragged paged-attention kernel vs the gathered dense fallback,
    µs per call, at the two serving shapes that used to be dense-only: a
    prefill chunk (S = chunk) and the spec-verify ladder (S = K+1).  The
    A/B pins the SAME shapes through both paths via the dispatcher's
    _FORCE_PATH hook, so the delta is purely Pallas-kernel-walking-pages
    vs gather-every-page-then-masked-dense.  On CPU the kernel side runs
    in interpret mode — the numbers there are a smoke signal, not perf."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import decode_attention as da

    if on_accel:
        B, H, Hkv, D, ps, M = 8, 16, 8, 128, 128, 16  # 2k-token pool/slot
        shapes = (("prefill_chunk", 256, 1024), ("verify", 5, 1536))
        reps = 20
    else:
        B, H, Hkv, D, ps, M = 2, 4, 2, 128, 128, 4
        shapes = (("prefill_chunk", 128, 256), ("verify", 5, 200))
        reps = 2

    rng = np.random.RandomState(0)
    P = 1 + B * M  # page 0 is the trash page
    kp = jnp.asarray(rng.randn(P, Hkv, ps, D).astype(np.float32) * 0.3)
    vp = jnp.asarray(rng.randn(P, Hkv, ps, D).astype(np.float32) * 0.3)
    pt = jnp.asarray(
        [[1 + b * M + j for j in range(M)] for b in range(B)], jnp.int32)

    out = {"ragged_attn_batch": B, "ragged_attn_pages_per_slot": M}
    for tag, S, off in shapes:
        q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)
        offs = jnp.full((B,), off, jnp.int32)

        def run(force):
            da._FORCE_PATH = force
            try:
                f = jax.jit(lambda qq: da.paged_decode_attention(
                    qq, kp, vp, offs, pt))
                _ = np.asarray(f(q))  # compile
                t0 = time.perf_counter()
                for _i in range(reps):
                    r = f(q)
                _ = np.asarray(r)
                return (time.perf_counter() - t0) / reps * 1e6
            finally:
                da._FORCE_PATH = None

        kern_us, dense_us = run(None), run("dense")
        out[f"ragged_attn_{tag}_kernel_us"] = round(kern_us, 1)
        out[f"ragged_attn_{tag}_dense_us"] = round(dense_us, 1)
        out[f"ragged_attn_{tag}_speedup"] = round(
            dense_us / max(kern_us, 1e-9), 2)
    return out


def _bench_llama7b_layer(on_accel):
    """One LLaMA-2-7B-dimension decoder layer (h=4096, ffn=11008, 32 heads)
    fwd+bwd at seq 2048 — anchors per-layer ms for BASELINE config #5 (the
    7B tp+pp+sharding run a single chip cannot hold; 32 layers x this
    number ~= the per-chip compute slice).  Ref: BASELINE.md:30."""
    if not on_accel:
        return {}
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama import LlamaDecoderLayer, _rope_cache
    from paddle_tpu.tensor.tensor import Tensor

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=1, num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=2048, dtype="bfloat16",
        tensor_parallel=False, use_flash_attention=True)
    paddle.seed(0)
    layer = LlamaDecoderLayer(cfg)
    layer.bfloat16()
    params, buffers = layer.functional_state()
    cos, sin = _rope_cache(128, 2048, cfg.rope_theta)
    B, S = 1, 2048

    def fwd_loss(params, x):
        from paddle_tpu.autograd import tape as _tape

        restore = layer.bind_functional_state(params, buffers)
        try:
            with _tape.no_grad():  # whole-function AD, the TrainStep pattern
                out = layer(Tensor(x), (Tensor(cos), Tensor(sin)))
        finally:
            restore()
        return jnp.sum(out._value.astype(jnp.float32) ** 2)

    # grad wrt params AND x: the full 6N train backward (dW matmuls
    # included — r3 differentiated x only, overstating the layer TF/s)
    step = jax.jit(jax.grad(fwd_loss, argnums=(0, 1)))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, 4096) * 0.02, jnp.bfloat16)
    _, g = step(params, x)
    float(jnp.sum(g[:1, :1, :1].astype(jnp.float32)))
    iters = 20
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            _, g = step(params, g)  # chain to keep the device busy
        float(jnp.sum(g[:1, :1, :1].astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    dt = best / iters
    n_params = sum(int(np.prod(p.shape)) for p in layer.parameters())
    # fwd 2N + bwd 4N per token + attention 3*(2*2*B*S^2*h)/2 causal
    flops = 6 * n_params * B * S + 3 * 2 * B * S * S * 4096
    return {"llama7b_layer_ms": round(dt * 1000, 2),
            "llama7b_layer_tfs": round(flops / dt / 1e12, 1)}


def _bench_llama_h4096(on_accel):
    """LLaMA pretrain MFU at the 7B shape (h=4096, ffn=11008, seq 2048) —
    as many layers as one chip's HBM holds with AdamW state.  The 738M
    h=2048 headline config is small-dim-limited; this is the MFU number at
    BASELINE config #5's actual hidden sizes (BASELINE.md:30)."""
    if not on_accel:
        return {}
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    for layers, batch in ((5, 4), (4, 4), (4, 2)):
        try:
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                num_hidden_layers=layers, num_attention_heads=32,
                num_key_value_heads=32, max_position_embeddings=2048,
                dtype="bfloat16", tensor_parallel=False,
                use_flash_attention=True)
            paddle.seed(0)
            model = LlamaForCausalLM(cfg)
            model.bfloat16()
            opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                         parameters=model.parameters())

            def loss_fn(ids, labels):
                logits = model(ids)
                return paddle.nn.functional.cross_entropy(
                    logits.reshape([-1, cfg.vocab_size]),
                    labels.reshape([-1]))

            step = paddle.jit.TrainStep(model, loss_fn, opt)
            seq, steps = 2048, 6
            ids = paddle.to_tensor(
                np.random.randint(0, cfg.vocab_size, (batch, seq), np.int32))
            labels = paddle.to_tensor(
                np.random.randint(0, cfg.vocab_size, (batch, seq), np.int32))
            for _ in range(2):
                loss = step(ids, labels)
            float(loss.item())
            windows = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = step(ids, labels)
                float(loss.item())
                windows.append(time.perf_counter() - t0)
            dt = sorted(windows)[1]
            n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
            tokens = batch * seq
            attn_flops = 3 * 2 * batch * seq * seq * cfg.hidden_size * layers
            flops_per_step = 6 * n_params * tokens + attn_flops
            mfu = (flops_per_step * steps / dt) / V5E_PEAK_FLOPS
            return {"llama_h4096_mfu": round(mfu, 4),
                    "llama_h4096_layers": layers,
                    "llama_h4096_tokens_per_sec": round(tokens * steps / dt, 1),
                    "llama_h4096_n_params": n_params}
        except Exception as e:
            last = repr(e)[:200]
    return {"llama_h4096_error": last}


def _bench_ernie(on_accel):
    """ERNIE/BERT-base MLM+NSP pretrain — THE driver north-star metric
    (BASELINE.md:22: 'ERNIE-3.0 tokens/sec/chip').

    Runs the REFERENCE pretrain recipe: masked_lm_positions with
    max_predictions_per_seq = 20 (create_pretraining_data's 15% of seq 128),
    MLM head over the gathered masked rows only.  FLOPs are accounted
    HONESTLY for that recipe — encoder matmuls on all B*S tokens, MLM
    transform+decoder on the B*20 masked rows, bidirectional attention term —
    NOT the dense 6*N*T upper bound (which would overstate MFU ~1.19x for
    work the masked head never does).  Not measured on the attached chip
    yet (PERF.md); tools/ernie_breakdown.py is the per-layer breakdown."""
    if not on_accel:
        return {}
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, ErnieForPretraining

    cfg = BertConfig.base()
    paddle.seed(0)
    model = ErnieForPretraining(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    batch, seq, n_pred, steps = 512, 128, 20, 8

    def loss_fn(ids, seg, pos, labels, nsp):
        loss, _ = model(ids, token_type_ids=seg, masked_lm_labels=labels,
                        next_sentence_label=nsp, masked_positions=pos)
        return loss

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    seg = paddle.to_tensor((rng.rand(batch, seq) > 0.5).astype(np.int32))
    pos = paddle.to_tensor(np.stack(
        [rng.choice(seq, n_pred, replace=False) for _ in range(batch)]).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, n_pred)).astype(np.int32))
    nsp = paddle.to_tensor(rng.randint(0, 2, (batch, 1)).astype(np.int32))
    for _ in range(2):
        loss = step(ids, seg, pos, labels, nsp)
    float(loss.item())
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids, seg, pos, labels, nsp)
        float(loss.item())
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[1]
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tokens = batch * seq
    rows_masked = batch * n_pred
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    # matmul param counts (weights only; gathers/biases excluded)
    enc_matmul = L * (h * 3 * h + h * h + 2 * h * cfg.intermediate_size)
    head_matmul = h * h + h * cfg.vocab_size        # transform + tied decoder
    pooled_matmul = h * h + h * 2                   # pooler + NSP head
    attn_flops = 3 * 4 * batch * seq * seq * h * L  # bidirectional (no causal /2)
    flops_per_step = (6 * enc_matmul * tokens + 6 * head_matmul * rows_masked
                      + 6 * pooled_matmul * batch + attn_flops)
    return {"ernie_tokens_per_sec_per_chip": round(tokens * steps / dt, 1),
            "ernie_mfu": round((flops_per_step * steps / dt) / V5E_PEAK_FLOPS, 4),
            "ernie_n_params": n_params,
            "ernie_batch_seq": [batch, seq],
            "ernie_masked_per_seq": n_pred,
            "ernie_step_ms": round(dt / steps * 1e3, 1),
            "ernie_flops_per_step": flops_per_step}


def _bench_vit(on_accel):
    """ViT-base/16 ImageNet training throughput (BASELINE config #2)."""
    if not on_accel:
        return {}
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import vit_b_16

    paddle.seed(0)
    model = vit_b_16(num_classes=1000)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.05,
                                 parameters=model.parameters())
    ce = paddle.nn.CrossEntropyLoss()
    batch, steps = 128, 10

    def loss_fn(x, y):
        return ce(model(x).astype("float32"), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(
        np.random.rand(batch, 3, 224, 224).astype(np.float32) * 2 - 1,
        dtype="bfloat16")
    y = paddle.to_tensor(np.random.randint(0, 1000, (batch,), np.int32))
    for _ in range(3):
        loss = step(x, y)
    float(loss.item())
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y)
        float(loss.item())
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[1]
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    toks = 197  # 14x14 patches + cls
    attn_flops = 3 * 4 * batch * toks * toks * 768 * 12
    flops_per_step = 6 * n_params * batch * toks + attn_flops
    ips = batch * steps / dt
    return {"vit_images_per_sec": round(ips, 1),
            "vit_mfu": round((flops_per_step * steps / dt) / V5E_PEAK_FLOPS, 4)}


def _bench_ocr(on_accel):
    """PP-OCR-style det+rec pipeline (BASELINE config #3): DBNet detection on
    640x640 pages + CRNN recognition of the cropped text lines (4 crops per
    page at the standard 32x320 rec shape), end-to-end inference."""
    if not on_accel:
        return {}
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.vision import ocr

    paddle.seed(0)
    det = ocr.DBNet(backbone_scale=0.5, arch="small", neck_channels=96)
    det.bfloat16()
    det.eval()
    rec = ocr.CRNN(num_classes=6625, hidden_size=48)
    rec.bfloat16()
    rec.eval()
    B, crops_per_page = 8, 4
    rng = np.random.RandomState(0)
    pages = paddle.to_tensor(rng.rand(B, 3, 640, 640).astype(np.float32),
                             dtype="bfloat16")
    lines = paddle.to_tensor(
        rng.rand(B * crops_per_page, 3, 32, 320).astype(np.float32),
        dtype="bfloat16")

    from paddle_tpu.autograd import tape as _tape

    def run(pg, ln):
        with _tape.no_grad():
            maps = det(paddle.Tensor(pg))  # DBHead returns {"maps": ...}
            logits = rec(paddle.Tensor(ln))
        m = maps["maps"] if isinstance(maps, dict) else maps
        return m._value, logits._value

    import jax.numpy as jnp

    def _sync(m):
        # fetch a device-side SCALAR: np.asarray(m) would copy the full
        # [8, 3, 640, 640] maps (~20 MB) to the host per window
        float(jnp.sum(m.reshape(-1)[:2].astype(jnp.float32)))

    jrun = jax.jit(run)
    m, lg = jrun(pages._value, lines._value)
    _sync(m); _sync(lg)
    steps = 40  # window >> the cost of the one sync that ends it
    windows = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(steps):
            m, lg = jrun(pages._value, lines._value)
        _sync(m)
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[2]
    return {"ocr_e2e_images_per_sec": round(B * steps / dt, 1),
            "ocr_det_batch": B, "ocr_rec_lines_per_page": crops_per_page}


def _bench_resnet(on_accel):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    batch = 128 if on_accel else 8
    img = 224 if on_accel else 64
    steps = 20 if on_accel else 2
    warmup = 5 if on_accel else 1

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    if on_accel:
        model.bfloat16()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    ce = nn.CrossEntropyLoss()

    def loss_fn(x, y):
        logits = model(x)
        return ce(logits.astype("float32"), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(np.random.rand(batch, 3, img, img).astype(np.float32) * 2 - 1,
                         dtype="bfloat16" if on_accel else "float32")
    y = paddle.to_tensor(np.random.randint(0, 1000, (batch,), np.int32))

    for _ in range(warmup):
        loss = step(x, y)
    float(loss.item())
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y)
        float(loss.item())
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[1]

    ips = batch * steps / dt
    # ResNet-50 fwd ~= 4.1 GFLOP/img at 224^2 (2*MACs); train ~= 3x fwd
    mfu = (ips * 3 * 4.1e9) / V5E_PEAK_FLOPS
    return {"resnet50_images_per_sec": round(ips, 2), "resnet50_mfu": round(mfu, 4)}


def _bench_observability(on_accel):
    """Telemetry overhead guard (ISSUE 5): per-step wall-time delta of the
    instrumented train step vs `observability.disable()` on the SAME
    compiled program — future BENCH rounds catch a telemetry regression as
    obs_overhead_us_per_step drifting up.  Runs on CPU too (the
    instrumentation cost is host-side by construction)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs

    batch, hidden = (256, 1024) if on_accel else (32, 64)
    steps = 60 if on_accel else 30

    paddle.seed(0)
    model = nn.Linear(hidden, hidden)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())

    def loss_fn(x, y):
        return paddle.nn.functional.mse_loss(model(x), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(
        np.random.rand(batch, hidden).astype(np.float32))
    y = paddle.to_tensor(
        np.random.rand(batch, hidden).astype(np.float32))

    def window():
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y)
        float(loss.item())
        return (time.perf_counter() - t0) / steps

    out = {}
    try:
        step(x, y)  # compile outside both windows
        # median of 3 per mode, interleaved so allocator/thermal drift
        # lands on both sides
        on_s, off_s = [], []
        for _ in range(3):
            obs.enable()
            on_s.append(window())
            obs.disable()
            off_s.append(window())
        on_med, off_med = sorted(on_s)[1], sorted(off_s)[1]
        out["obs_overhead_us_per_step"] = round((on_med - off_med) * 1e6, 2)
        out["obs_overhead_frac"] = round(
            (on_med - off_med) / off_med, 5) if off_med > 0 else 0.0
        out["obs_disabled_us_per_step"] = round(off_med * 1e6, 2)
    finally:
        obs.enable()
    return out


def _bench_goodput(on_accel):
    """Goodput-ledger overhead guard (ISSUE 20): cost of one
    section+carve+token step on an enabled vs disabled ledger.  The
    ledger sits inside the engine tick and the recovery step loop, so
    its enabled cost must stay in single-digit microseconds and its
    disabled cost at ~one dict lookup — a regression here taxes every
    step of every instrumented run.  Host-side by construction: runs on
    CPU too."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import goodput

    iters = 20000 if on_accel else 5000

    def window(led):
        t0 = time.perf_counter()
        for _ in range(iters):
            with led.section("step"):
                led.carve("compile", 1e-9)
            led.count_tokens("useful", 1)
        return (time.perf_counter() - t0) / iters

    out = {}
    try:
        # median of 3 per mode, interleaved so drift lands on both sides
        on_s, off_s = [], []
        for _ in range(3):
            obs.enable()
            on_s.append(window(goodput.TimeLedger("train")))
            obs.disable()
            off_s.append(window(goodput.TimeLedger("train")))
        on_med, off_med = sorted(on_s)[1], sorted(off_s)[1]
        out["goodput_overhead_us_per_step"] = round(on_med * 1e6, 3)
        out["goodput_disabled_us_per_step"] = round(off_med * 1e6, 3)
    finally:
        obs.enable()
    return out


def _bench_xplane_parse(on_accel):
    """Profiling-plane cost guard (ISSUE 14): wire-parse + per-op
    aggregation throughput of the dependency-free XPlane reader over a
    realistic blob (the committed golden dump replicated 64x —
    concatenated XSpace serializations merge, so the blob is one legal
    multi-plane dump).  trace_report --xplane runs at operator cadence,
    but a regression from linear to quadratic (span copies, repeated
    metadata resolution) would make real multi-GB TPU dumps unusable.
    Host-side by construction: runs on CPU too."""
    import os

    from paddle_tpu.observability import xplane

    golden = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "data", "golden.xplane.pb")
    with open(golden, "rb") as f:
        blob = f.read() * 64

    def med(fn, n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    parse_s = med(lambda: xplane.parse_xspace(blob), 9)
    space = xplane.parse_xspace(blob)
    summ_s = med(lambda: xplane.device_seconds(space), 9)
    mb = len(blob) / 1e6
    return {
        "xplane_parse_us_per_mb": round(parse_s * 1e6 / mb, 1),
        "xplane_summary_us_per_mb": round(summ_s * 1e6 / mb, 1),
        "xplane_bench_ops": len(xplane.to_timeline(space)),
    }


def _bench_roofline(on_accel):
    """Roofline-plane cost guard (ISSUE 17): residual-join throughput —
    µs per MB of dump to go from a parsed XSpace + census to the sorted
    residual table (predict + match + rank).  Companion to
    xplane_summary_us_per_mb: the sentinel runs at CI cadence over real
    multi-GB dumps, so the join must stay linear in ops.  Host-side by
    construction: runs on CPU too."""
    import os

    from paddle_tpu.observability import roofline, xplane

    golden = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "data", "golden.xplane.pb")
    with open(golden, "rb") as f:
        blob = f.read() * 64
    measured = xplane.to_timeline(xplane.parse_xspace(blob))
    # synthetic census covering every measured op (worst-case: every row
    # matches, nothing early-outs) plus prefixed variants to exercise the
    # containment fallback
    census = {}
    for i, name in enumerate(measured):
        census[name.rsplit("/", 1)[-1]] = {
            "opcode": "dot", "flops": 1e9 * (i + 1), "bytes": 1e6 * (i + 1)}

    def med(fn, n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    join_s = med(lambda: roofline.residual_rows(measured, census,
                                                197e12, 819e9), 9)
    mb = len(blob) / 1e6
    return {
        "roofline_join_us_per_mb": round(join_s * 1e6 / mb, 1),
        "roofline_bench_ops": len(measured),
    }


def _profile_roofline(on_accel, round_name=None):
    """bench --profile: the measured-vs-predicted loop (ISSUE 17).

    Two deliberately opposite configs — a gemm scan chain that should pin
    the compute roof and a streaming reduce that should pin the memory
    roof — each compiled once (the same executable feeds
    census.per_op_census AND the profiled window), wrapped in a
    ProfilingSession, joined into per-config residual reports, merged
    into ONE content-addressed round, and (with --round) persisted as
    ROOFLINE_<round>.json for the sentinel to diff against.  Residual
    tables go to stderr (stdout stays the one-JSON-line contract)."""
    import os
    import sys

    import jax
    import jax.numpy as jnp

    from paddle_tpu import cost_model
    from paddle_tpu.distributed import census as _census
    from paddle_tpu.observability import profiling, roofline

    pf = cost_model.peak_flops_per_device()
    pbw = cost_model.peak_hbm_bytes_per_sec()
    if pbw <= 0:  # unknown host (CPU): explicit measured fallback
        pbw = cost_model.peak_hbm_bytes_per_sec(measure=True)
    if pf <= 0:
        # small-scale gemm probe (the 8192^2 hw probe is accelerator
        # budget): enough to anchor CPU rounds, spec table rules on TPU
        n = 1024
        x = jnp.ones((n, n), jnp.float32)

        @jax.jit
        def chain(x):
            def body(c, _):
                return c @ x, ()
            return jax.lax.scan(body, x, None, length=8)[0]

        jax.block_until_ready(chain(x))
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x))
        dt = time.perf_counter() - t0
        pf = 8 * 2 * n ** 3 / dt if dt > 0 else 0.0

    d = 2048 if on_accel else 512
    m = 1 << (26 if on_accel else 22)  # streaming vector elements
    steps = 8
    dtype = jnp.bfloat16 if on_accel else jnp.float32

    def gemm_chain(x, w):
        # unrolled on purpose: a lax.scan hides the dots inside the
        # while-body computation, which the entry-only census can't cost
        for _ in range(4):
            x = x @ w
        return x

    def stream_reduce(a, b):
        return jnp.sum(jnp.abs(a + b), dtype=jnp.float32)

    configs = {
        "gemm": (gemm_chain, (jnp.ones((d, d), dtype) * 0.01,
                              jnp.ones((d, d), dtype) * 0.01),
                 {"kind": "gemm_scan_chain", "d": d, "depth": 4,
                  "dtype": str(jnp.dtype(dtype)), "steps": steps}),
        "stream": (stream_reduce, (jnp.ones((m,), dtype),
                                   jnp.ones((m,), dtype)),
                   {"kind": "stream_abs_sum", "elems": m,
                    "dtype": str(jnp.dtype(dtype)), "steps": steps}),
    }

    out = {}
    reports = {}
    for name, (fn, args, cfg) in configs.items():
        compiled = jax.jit(fn).lower(*args).compile()
        cens = _census.per_op_census(compiled)
        r = compiled(*args)
        jax.block_until_ready(r)  # warm before the profiled window
        with profiling.ProfilingSession() as prof:
            for _ in range(steps):
                r = compiled(*args)
            jax.block_until_ready(r)
        rep = roofline.build_report(prof.summary, cens, pf, pbw,
                                    config=cfg)
        reports[name] = rep
        s = rep["summary"]
        print(f"--- roofline[{name}] ---", file=sys.stderr)
        print(roofline.render_text(rep, top=10), file=sys.stderr)
        out[f"roofline_{name}_residual_ratio"] = s["residual_ratio"]
        out[f"roofline_{name}_wasted_us"] = s["wasted_us"]
        out[f"roofline_{name}_ops"] = s["ops"]
    merged = roofline.merge_reports(reports)
    roofline.export_gauges(merged)
    out["roofline_round_key"] = merged["key"]
    out["roofline_peak_flops_per_sec"] = round(pf, 1)
    out["roofline_peak_hbm_bytes_per_sec"] = round(pbw, 1)
    if round_name:
        root = os.path.dirname(os.path.abspath(__file__))
        out["roofline_round_path"] = roofline.save_round(
            merged, root, round_name)
        print(f"persisted roofline round {round_name} "
              f"(key {merged['key']})", file=sys.stderr)
    return out


def _bench_alerting(on_accel):
    """Alerting-plane cost guard (ISSUE 7): exposition parse cost of a
    realistic scraped payload and rule-evaluation cost per engine tick
    over the default rule set — the companions to
    obs_overhead_us_per_step, so the sense/decide loop can't quietly grow
    into a hot-path tax.  Host-side by construction: runs on CPU too."""
    from paddle_tpu.observability import alerts, metrics, scrape, slo

    # a realistic fleet payload: the full instrumented registry (the
    # process importing bench has llm/train/store series registered) plus
    # synthetic per-replica series to hit fleet-scale label cardinality
    reg = metrics.REGISTRY
    for i in range(8):
        slo.track(f"bench_alert_series_{i}", 0.01 * (i + 1))
    synth = metrics.MetricRegistry()
    g = synth.gauge("bench_fleet_depth", "synthetic", labelnames=("rank",))
    h = synth.histogram("bench_fleet_seconds", "synthetic",
                        labelnames=("rank",))
    for rank in range(16):
        g.labels(rank=str(rank)).set(rank * 3.0)
        for k in range(8):
            h.labels(rank=str(rank)).observe(0.001 * (k + 1))
    payload = reg.render_prometheus() + synth.render_prometheus()

    def med(fn, n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    parse_s = med(lambda: scrape.parse_prometheus(payload), 9)
    families = scrape.parse_prometheus(payload)
    samples = scrape.SampleSet().add_families(families, {"target": "t0"})

    rules = alerts.default_rules() + [
        alerts.Rule("bench_backlog", metric="bench_fleet_depth", op=">",
                    threshold=30.0, for_s=5.0),
        alerts.Rule("bench_rising", kind="delta",
                    metric="bench_fleet_seconds_count", op=">",
                    threshold=100.0, window_s=60.0),
    ]
    engine = alerts.AlertEngine(rules=rules, clock=lambda: 0.0)
    tick = {"t": 0.0}

    def one_tick():
        tick["t"] += 1.0
        engine.evaluate(samples, now=tick["t"])

    one_tick()  # first tick builds the instance cells
    eval_s = med(one_tick, 50)
    return {
        "alert_parse_us_per_scrape": round(parse_s * 1e6, 1),
        "alert_eval_us_per_tick": round(eval_s * 1e6, 1),
        "alert_scrape_samples": len(samples),
        "alert_rules_count": len(rules),
    }


def _bench_tracing(on_accel):
    """Request-tracing cost guard (ISSUE 8): per-request overhead of the
    full traced lifecycle (start -> queue_wait -> admission span -> 4
    prefill-chunk spans -> coalesced decode summary -> end + tail-sample
    offer) in three modes — enabled-and-kept, enabled-but-sampled-out,
    and observability disabled — next to obs_overhead_us_per_step, so the
    forensic plane can't quietly grow into a hot-path tax.  Host-side by
    construction: runs on CPU too."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import tracing

    n = 4000 if on_accel else 1500
    hist = obs.metrics.MetricRegistry().histogram(
        "bench_trace_seconds", "synthetic")

    def lifecycle(tracer):
        t = tracer.start_trace("llm_request", prompt_tokens=128,
                               max_new_tokens=32)
        t.add_span("queue_wait", duration_s=1e-4)
        adm = t.span("admission", slot=0, episode=1,
                     cached_tokens=64).open()
        for i in range(4):
            with t.span("llm_prefill_chunk", index=i, tokens=32):
                pass
        adm.close()
        t.add_span("decode", duration_s=1e-3, ticks=32, tokens=32)
        hist.observe(1e-3, exemplar=t.trace_id or None)
        t.end("ok", generated_tokens=32)

    def window(tracer, reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            lifecycle(tracer)
        return (time.perf_counter() - t0) / reps

    def baseline_window(reps):
        # the same loop shape with NO tracer calls: what "tracing absent"
        # costs, the disabled mode's comparison floor
        t0 = time.perf_counter()
        for _ in range(reps):
            hist.observe(1e-3)
        return (time.perf_counter() - t0) / reps

    out = {}
    try:
        obs.enable()
        kept = tracing.Tracer(store=tracing.TraceStore(
            capacity=64, sample_every=1))
        sampled_out = tracing.Tracer(store=tracing.TraceStore(
            capacity=64, sample_every=0))  # healthy traces all dropped
        med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
        kept_s, samp_s, dis_s, base_s = [], [], [], []
        for _ in range(3):  # interleaved medians, like _bench_observability
            obs.enable()
            kept_s.append(window(kept, n))
            samp_s.append(window(sampled_out, n))
            base_s.append(baseline_window(n))
            obs.disable()
            dis_s.append(window(kept, n))
        out["trace_overhead_us_per_request_enabled"] = round(
            med(kept_s) * 1e6, 3)
        out["trace_overhead_us_per_request_sampled_out"] = round(
            med(samp_s) * 1e6, 3)
        out["trace_overhead_us_per_request_disabled"] = round(
            med(dis_s) * 1e6, 3)
        out["trace_overhead_us_per_request_baseline"] = round(
            med(base_s) * 1e6, 3)
    finally:
        obs.enable()
    return out


def _bench_router(on_accel):
    """Serving-plane guard (ISSUE 12): the SAME deterministic
    shared-prefix trace routed through 2 in-process replicas by the
    prefix-affinity router vs alternated round-robin — affinity must win
    on fleet-wide prefix-cache hit ratio — plus the router's own
    per-request overhead (placement decision + admission ack over the
    wire), so the front door can't quietly grow into a serving tax.
    Host-side by construction: runs on CPU too."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.router import ReplicaServer, Router
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(tensor_parallel=False,
                           use_flash_attention=False)
    ps, slots, n_req, new_toks = 16, 2, 8 if on_accel else 6, 4
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    head = rng.randint(0, cfg.vocab_size, 2 * ps).astype(np.int32)
    prompts = [np.concatenate([head,
                               rng.randint(0, cfg.vocab_size, ps // 2)
                               .astype(np.int32)]) for _ in range(n_req)]

    def engine():
        return LLMEngine(model, max_batch_slots=slots, max_seq_len=128,
                         kv_layout="paged", page_size=ps,
                         prefill_chunk=ps, metrics_port=0)

    def fleet_hit_ratio(engines):
        hit = sum(e.stats()["prefix_cache"]["hit_tokens"] for e in engines)
        tot = sum(e.stats()["prefix_cache"]["prompt_tokens"]
                  for e in engines)
        return hit / tot if tot else 0.0

    # affinity-routed pass: live wire path through 2 replicas
    reps = [ReplicaServer(engine(), name=f"bench-r{i}") for i in range(2)]
    for r in reps:
        r.engine.start()
    router = Router(reps, page_size=ps, affinity_blocks=4)
    try:
        t0 = time.perf_counter()
        for p in prompts:
            router.request(p, max_new_tokens=new_toks, timeout=120)
        dt = max(time.perf_counter() - t0, 1e-6)
        rz = router.routerz()
        aff_ratio = fleet_hit_ratio([r.engine for r in reps])
    finally:
        router.stop()
        for r in reps:
            r.engine.stop()

    # round-robin baseline: the SAME trace alternated across fresh engines
    rr = [engine(), engine()]
    try:
        futs = [rr[i % 2].submit(p, max_new_tokens=new_toks)
                for i, p in enumerate(prompts)]
        for e in rr:
            e.run_until_complete()
        for f in futs:
            f.result(timeout=1)
        rr_ratio = fleet_hit_ratio(rr)
    finally:
        for e in rr:
            e.stop()
    return {
        "router_affinity_hit_ratio": round(rz["affinity"]["hit_ratio"], 4),
        "router_prefix_cache_hit_ratio": round(aff_ratio, 4),
        "router_prefix_cache_hit_ratio_round_robin": round(rr_ratio, 4),
        "router_overhead_us_per_request": rz["overhead_us_mean"],
        "router_trace_requests": n_req,
        "router_trace_tokens_per_sec": round(n_req * new_toks / dt, 1),
    }


def _bench_tpulint(on_accel):
    """Static-analysis cost guard (ISSUE 18): tpulint file-rule throughput
    in microseconds per thousand source lines over the real package.  The
    pre-commit loop budget is "sub-second for a spot-lint"; a rule that
    re-walks the AST per node (quadratic) or re-parses per rule would blow
    that silently while --check still passes.  Runs the engine in-process
    (serial, file rules only — project rules import jax and are bounded by
    compile time, not lint time).  Host-only by construction."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "_bench_tpulint_analysis",
        os.path.join(repo, "paddle_tpu", "analysis", "__init__.py"),
        submodule_search_locations=[
            os.path.join(repo, "paddle_tpu", "analysis")])
    analysis = importlib.util.module_from_spec(spec)
    import sys as _sys
    _sys.modules["_bench_tpulint_analysis"] = analysis
    spec.loader.exec_module(analysis)

    pairs = analysis.list_target_files(repo, ["paddle_tpu"])
    kloc = sum(sum(1 for _ in open(a, "rb")) for a, _ in pairs) / 1000.0

    def run():
        project = analysis.ProjectContext(repo)
        file_rules = [r for r in analysis.RULES.values()
                      if isinstance(r, analysis.FileRule)]
        n = 0
        for abspath, relpath in pairs:
            n += len(analysis.lint_file(project, abspath, relpath,
                                        file_rules))
        return n

    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    med = sorted(ts)[len(ts) // 2]
    return {
        "tpulint_us_per_kloc": round(med * 1e6 / max(kloc, 1e-9), 1),
        "tpulint_bench_kloc": round(kloc, 1),
        "tpulint_bench_rules": len(analysis.RULES),
    }


def _bench_multi_tenant(on_accel):
    """Multi-tenant serving guard (ISSUE 15): the SAME deterministic trace
    decoded three ways — every request on its own adapter (the mixed
    many-tenant case the paged pool exists for), every request on ONE
    adapter, and a no-adapter base engine — so the batched-gather
    epilogue's cost and the adapter-MIX penalty (which must be ~zero:
    only the gather rows change) are both pinned.  Plus the host-side
    constraint-mask cost per decode tick (automaton mask + device
    upload), since that's the only per-tick work constrained decoding
    adds.  Host/gather-bound by construction: runs on CPU too."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.constrain import compile_constraint
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.lora import (AdapterRegistry, LoraAdapter,
                                        lora_sites)

    cfg = LlamaConfig.tiny(tensor_parallel=False,
                           use_flash_attention=False)
    n_adapters = 64 if on_accel else 12
    slots, n_req, new_toks, ps = 4, (16 if on_accel else 8), 8, 16
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    sites = lora_sites(model)
    adapters = {f"a{i}": LoraAdapter.random(sites, rank=4, seed=1000 + i)
                for i in range(n_adapters)}
    reg = AdapterRegistry.from_adapters(model, adapters, rank=4)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(n_req)]

    def run(eng, aids):
        futs = [eng.submit(p, max_new_tokens=new_toks, adapter_id=a)
                for p, a in zip(prompts, aids)]
        eng.run_until_complete()
        toks = sum(len(f.result(timeout=1)) for f in futs)
        return toks

    def timed(adapters_reg, aids):
        eng = LLMEngine(model, max_batch_slots=slots, max_seq_len=64,
                        kv_layout="paged", page_size=ps, prefill_chunk=ps,
                        adapters=adapters_reg)
        try:
            eng.warmup()
            run(eng, aids)  # prime the first-request eager-op compiles
            t0 = time.perf_counter()
            toks = run(eng, aids)
            return toks / max(time.perf_counter() - t0, 1e-6)
        finally:
            eng.stop()

    mixed_ids = [f"a{i % n_adapters}" for i in range(n_req)]
    mixed = timed(reg, mixed_ids)
    single = timed(reg, ["a0"] * n_req)
    base = timed(None, [None] * n_req)

    # host-side constraint cost per decode tick: advance-independent —
    # mask lookup for every slot + one [B, V] device upload, exactly what
    # the engine's constrained decode path does each tick
    tc = compile_constraint(r"[0-9]+", ["%d" % i if i < 10 else f"w{i}"
                                        for i in range(cfg.vocab_size)],
                            cfg.vocab_size - 1)
    cursors = [tc.cursor() for _ in range(slots)]
    iters = 50
    t0 = time.perf_counter()
    for _ in range(iters):
        mask = np.stack([c.mask() for c in cursors])
        jnp.asarray(mask).block_until_ready()
    mask_us = (time.perf_counter() - t0) / iters * 1e6

    return {
        "multi_tenant_adapters": n_adapters,
        "multi_tenant_mixed_tokens_per_sec": round(mixed, 1),
        "multi_tenant_single_adapter_tokens_per_sec": round(single, 1),
        "multi_tenant_base_tokens_per_sec": round(base, 1),
        "multi_tenant_mix_penalty_ratio": round(single / mixed, 3),
        "multi_tenant_lora_overhead_ratio": round(base / single, 3),
        "constraint_mask_us_per_tick": round(mask_us, 1),
    }


def main(argv=None):
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--profile", action="store_true",
                    help="also run the roofline measured-vs-predicted "
                         "loop (_profile_roofline): per-config "
                         "ProfilingSession windows joined against their "
                         "census into residual tables (stderr) and "
                         "roofline_* fields on the JSON line")
    ap.add_argument("--round", default=None,
                    help="with --profile: persist the merged round as "
                         "ROOFLINE_<NAME>.json next to bench.py (the "
                         "sentinel baseline)")
    args = ap.parse_args(argv)

    from paddle_tpu.core.device import enable_compile_cache

    enable_compile_cache()
    on_accel = jax.default_backend() not in ("cpu",)
    out = {}
    if on_accel:
        # measure the chip's gemm ceiling FIRST, on a clean HBM — after the
        # model benches the number is polluted by allocator state
        try:
            out["hw_gemm_tfs_measured"] = round(_measure_gemm_peak(), 1)
            out["hw_conv_tfs_measured"] = round(_measure_conv_peak(), 1)
            out["hw_hbm_gbs_measured"] = round(_measure_hbm_bw(), 0)
        except Exception as e:
            out["hw_peak_error"] = repr(e)[:200]
    # soft deadline: if the harness kills us mid-bench the whole JSON line
    # is lost, so stop starting new benches near the budget and print
    deadline = time.monotonic() + float(
        __import__("os").environ.get("BENCH_BUDGET_S", "2700"))
    for fn, tag in ((_bench_llama, "llama"),
                    (_bench_llama_h4096, "llama_h4096"),
                    (_bench_resnet, "resnet"),
                    (_bench_decode, "decode"),
                    (_bench_prefix_cache, "prefix_cache"),
                    (_bench_kv_tiers, "kv_tiers"),
                    (_bench_spec_decode, "spec_decode"),
                    (_bench_ragged_attention, "ragged_attention"),
                    (_bench_llama7b_layer, "llama7b_layer"),
                    (_bench_ernie, "ernie"),
                    (_bench_vit, "vit"),
                    (_bench_ocr, "ocr"),
                    (_bench_observability, "observability"),
                    (_bench_goodput, "goodput"),
                    (_bench_alerting, "alerting"),
                    (_bench_tracing, "tracing"),
                    (_bench_xplane_parse, "xplane"),
                    (_bench_roofline, "roofline"),
                    (_bench_router, "router"),
                    (_bench_multi_tenant, "multi_tenant"),
                    (_bench_tpulint, "tpulint")):
        if time.monotonic() > deadline:
            out[f"{tag}_skipped"] = "bench budget exhausted"
            continue
        try:
            out.update(fn(on_accel))
        except Exception as e:  # keep the line printable even if one bench dies
            out[f"{tag}_error"] = repr(e)[:300]

    if args.profile:
        try:
            out.update(_profile_roofline(on_accel, round_name=args.round))
        except Exception as e:
            out["roofline_profile_error"] = repr(e)[:300]

    # headline MFU: the 7B-shape (h=4096) config when it ran — BASELINE
    # config #5's hidden sizes — else the 738M config
    if out.get("llama_mfu") is not None:
        out["llama_738m_mfu"] = out["llama_mfu"]
    if out.get("llama_h4096_mfu"):
        out["llama_mfu"] = out["llama_h4096_mfu"]

    if on_accel and out.get("hw_gemm_tfs_measured") and out.get("llama_mfu"):
        out["llama_mfu_vs_measured_peak"] = round(
            out["llama_mfu"] * (V5E_PEAK_FLOPS / 1e12) / out["hw_gemm_tfs_measured"], 4)

    # ResNet vs the chip's own conv ability
    if on_accel and out.get("resnet50_images_per_sec") and out.get("hw_conv_tfs_measured"):
        eff = out["resnet50_images_per_sec"] * 3 * 4.1e9 / 1e12
        out["resnet50_effective_tfs"] = round(eff, 1)
        out["resnet50_frac_of_conv_ceiling"] = round(
            eff / out["hw_conv_tfs_measured"], 3)

    # decode roofline closure: floor = stream bytes / measured read bandwidth;
    # frac = floor / achieved (<= 1.0 when the accounting is consistent)
    bw = out.get("hw_hbm_gbs_measured")
    if on_accel and bw:
        for pre in ("llama_decode", "llama_decode_int8"):
            ms = out.get(f"{pre}_ms_per_token")
            gb = out.get(f"{pre}_stream_gb_per_tok")
            if ms and gb:
                floor = gb / bw * 1000
                out[f"{pre}_floor_ms_per_token"] = round(floor, 2)
                out[f"{pre}_roofline_frac"] = round(floor / ms, 3)

    mfu = out.get("llama_mfu", 0.0)
    print(json.dumps({
        "metric": "llama_pretrain_mfu" if on_accel else "llama_pretrain_mfu_cpu_smoke",
        "value": mfu,
        "unit": "model_flops_utilization",
        "vs_baseline": round(mfu / 0.70, 4),
        "timing": "median_of_3_windows",
        **out,
    }))


if __name__ == "__main__":
    main()
