"""Does paddle-tpu start on the attached TPU?  `python chip_smoke.py`

Drives the two normal entry points once at the full widths of
`LlamaConfig()` (h=4096, ffn 11008, 32x128 heads, vocab 32000) with depth
cut to N_LAYERS and seeded random weights:

  serve  LLMEngine(kv_layout="paged") answers 12 requests (README sequence)
  kernel paged_decode_attention vs its own dense path at the engine's shapes
  state  LLMEngine with a small MiniCPM-SALA (128-wide heads, so the block-
         sparse and the lightning kernels are the paths taken): a document,
         then two questions of it that resume from its state checkpoint
  latent LLMEngine with a small DeepSeek-V3-shaped model (latent 512 + rope
         64 a token, 8 gated experts of whole-lane width): a document, then
         two questions of it that map its latent pages (one forks a partial
         page), decode through the latent kernel
  granite LLMEngine with Granite-4.0-H at its published widths, one period
         deep (9 Mamba-2 layers, 1 attention layer of 64-wide heads), seeded:
         the paged kernel serves the 64-wide heads, the head is the embedding
  granite_moe  LLMEngine with Granite-4.0-H-Small's layer at its published
         widths (128 Mamba-2 heads, 72 router experts of which 8 are held,
         top 10, a shared expert), two layers (mamba, attention): the state
         kernel at 128 heads, the gated expert kernel at 4096 x 768, served
         tokens against the float32 reference
  profile a 2-layer engine under load, profiled for 1 s by itself
         (LLMEngine.profile_device) and reduced by its own census: every
         device second lands under a program the engine knows
  train  paddle.jit.TrainStep + AdamW, 3 steps at batch 4 x seq 2048
  mesh   ShardedTrainStep(zero_stage=2) on sharding=2 x mp=2 (>= 4 chips)

ONE process touches JAX; no network, nothing tracked written.  The script
starts no child.  The package starts one, once per fresh checkout: the g++
build of its host library (core/native), which LLMEngine.warmup() triggers
and which never imports JAX.  Any failed check raises: there is no retry at
a smaller size and no error field.  Off the chip (JAX_PLATFORMS=cpu, no TPU)
it exits non-zero before running anything.  On success the last stdout line
is {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time

import numpy as np

N_LAYERS = 4          # the only cut: LlamaConfig() is 32 layers deep
SEQ = 2048
T0 = time.perf_counter()


def log(msg):
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(f"chip_smoke: FAILED: {what}")
    log(f"ok: {what}")


def device_gate():
    """Refuse anything but a TPU both peak tables know."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"jax {jax.__version__} devices: {dev}")
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform="
                 f"{dev['platform']!r} ({dev['kind']}, {dev['count']} "
                 f"device(s)); nothing was run")
    from importlib import metadata

    from paddle_tpu import cost_model

    log(f"libtpu {metadata.version('libtpu')}")
    flops = cost_model.peak_flops_per_device()
    hbm = cost_model.peak_hbm_bytes_per_sec()
    check(flops > 0 and hbm > 0,
          f"peak tables know {dev['kind']!r}: {flops / 1e12:.0f} TFLOP/s, "
          f"{hbm / 1e9:.0f} GB/s")
    return dev


def _free():
    """Between phases: the finished phase's model, engine and executables
    are garbage only once its frame is gone (layers hold reference cycles),
    and the next phase needs the HBM."""
    import jax

    gc.collect()
    jax.clear_caches()


def _metric(name):
    """{label values: count} of one registry family."""
    from paddle_tpu.observability import metrics

    fam = metrics.REGISTRY.get(name)
    return {labels: child.value for labels, child in fam.series()} \
        if fam is not None else {}


def _llama(n_layers, tensor_parallel, **overrides):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(num_hidden_layers=n_layers, dtype="bfloat16",
                      tensor_parallel=tensor_parallel, **overrides)
    return cfg, LlamaForCausalLM(cfg).bfloat16()


# ------------------------------------------------------------------- serve
def serve_phase(n_layers=N_LAYERS, max_seq_len=SEQ, prompt_lens=(100, 1500),
                n_requests=12, new_tokens=32, timeout=600.0, **overrides):
    """README §"LLM serving" sequence; returns the engine geometry the
    kernel phase reuses."""
    from paddle_tpu.inference import LLMEngine

    cfg, model = _llama(n_layers, tensor_parallel=False, **overrides)
    model.eval()
    page, slots = 128, 8
    eng = LLMEngine(model, kv_layout="paged", page_size=page,
                    prefill_chunk=256, max_seq_len=max_seq_len,
                    max_batch_slots=slots)
    log(f"serve: {n_layers} layers, warmup() ...")
    log(f"serve: warmup took {eng.warmup():.1f}s")
    compiles = _metric("jit_compiles_total")

    rng = np.random.default_rng(0)
    lens = np.linspace(*prompt_lens, n_requests).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lens]
    # requests 1-3 open with one "system prompt" of 2 pages + 44 tokens: the
    # prefix cache must serve those pages once the first has prefilled
    system = rng.integers(0, cfg.vocab_size, 2 * page + 44, dtype=np.int32)
    for i in (1, 2, 3):
        tail = prompts[i][:max(len(prompts[i]) - len(system), 8)]
        prompts[i] = np.concatenate([system, tail])
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        outs = [f.result(timeout=timeout) for f in futures]
    finally:
        eng.stop()
    check(all(len(o) == new_tokens for o in outs),
          f"{n_requests} requests ({min(map(len, prompts))}.."
          f"{max(map(len, prompts))} prompt tokens, {slots} slots) each "
          f"returned {new_tokens} tokens")
    check(all(0 <= t < cfg.vocab_size for o in outs for t in o),
          "every token id is in the vocabulary")
    after = _metric("jit_compiles_total")
    check(after == compiles,
          f"no compile between warmup() and stop() (jit_compiles_total "
          f"{sum(compiles.values())} -> {sum(after.values())})")
    attn = _metric("llm_attn_kernel_total")
    paths = {labels[0] for labels in attn}
    check("paged_kernel" in paths and "paged_dense" not in paths,
          f"every paged attention site compiled to the Pallas kernel: {attn}")
    prefix = eng.stats()["prefix_cache"]
    check(prefix["hit_tokens"] > 0,
          f"prefix cache served {prefix['hit_tokens']} of "
          f"{prefix['prompt_tokens']} prompt tokens")

    # reported, not asserted: random weights flip argmax on rounding
    import paddle_tpu as paddle

    ref = np.asarray(model.generate(
        paddle.to_tensor(prompts[0][None]), max_new_tokens=new_tokens)._value)[0]
    agree = int(np.cumprod(ref == np.asarray(outs[0])).sum())
    log(f"serve: request 0 agrees with model.generate() for {agree} of "
        f"{new_tokens} leading tokens")
    pl = _ran_ahead(eng)
    # one program carried a chunk AND decode rows, and no admission made the
    # pump read before it dispatched: every program but an idle spell's
    # first went out with the one before still unread, and a first token's
    # read found the logits there (a transfer, not a program's wait)
    ph = eng.stats()["tick_phases"]
    cnt, sec = ph["count"], ph["seconds"]
    sent = cnt["decode_dispatch"] + cnt["prefill_dispatch"]
    check(pl["mixed"] > 0, f"chunks rode beside decoding rows: {pl}")
    check(pl["overlapped"] >= 0.99 * (sent - pl["drained"]["idle"]),
          f"{pl['overlapped']} of {sent} programs were dispatched with the "
          f"one before unread ({pl['drained']['idle']} idle spells)")
    first_ms = 1e3 * sec["first_token_sync"] / cnt["first_token_sync"]
    sync_ms = 1e3 * sec["decode_sync"] / cnt["decode_sync"]
    check(first_ms < max(0.5 * sync_ms, 2.0),
          f"a first token is read in {first_ms:.2f} ms (a program's wait: "
          f"{sync_ms:.2f} ms)")
    # the short requests against the model's own one-pass logits
    short = [i for i, p in enumerate(prompts) if len(p) <= 640]
    _one_pass_gap(model, [prompts[i] for i in short], [outs[i] for i in short])
    geom = dict(heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads,
                head_dim=cfg.hidden_size // cfg.num_attention_heads,
                page=page, slots=slots, num_pages=eng.num_pages,
                max_pages=eng.M, chunk=eng.prefill_chunk)
    return geom


def _ran_ahead(eng):
    """The pump kept a decode program in flight: a later change that quietly
    reads every result before it dispatches the next shows here."""
    pl = eng.stats()["tick_pipeline"]
    check(pl["overlapped"] > 0, "decode results were read with the next "
          f"program dispatched (stats()['tick_pipeline']): {pl}")
    return pl


def _one_pass_gap(model, asks, outs, tol=0.05):
    """How far the served tokens lie below the best of the model's own
    one-pass logits (the chunk path: no decode kernel), at worst."""
    import jax.numpy as jnp

    worst = 0.0
    for p, o in zip(asks, outs):
        both = np.concatenate([p, np.asarray(o, np.int32)])
        lg = np.asarray(model.forward(jnp.asarray(both[None]))._value[0],
                        np.float32)[len(p) - 1:len(both) - 1]
        worst = max(worst, float(np.max(lg.max(-1) - lg[np.arange(len(o)), o])))
    # bfloat16 logits of size ~0.5: a served token may lose to a neighbour
    # by a rounding step or two, not by more
    check(worst < tol, f"served tokens lie within {worst:.4f} (< {tol}) of the "
          "one-pass logits' best")
    return worst


# ------------------------------------------------------------------- state
def state_phase(doc_pages=3, new_tokens=8, hidden=256, timeout=600.0):
    """A model with recurrent state beside paged attention, its prefix cache
    on: a document that ends on a page boundary leaves a state checkpoint,
    two questions of it resume there, and the tokens the decode kernels gave
    lie at the top of the model's own one-pass logits (the chunk path: no
    decode kernel)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.minicpm_sala import (LIGHTNING, SPARSE,
                                                MiniCPMSALAConfig,
                                                MiniCPMSALAForCausalLM)
    from paddle_tpu.ops.sparse_attention import SparseSpec

    paddle.seed(0)
    page = 128
    cfg = MiniCPMSALAConfig(
        vocab_size=512, hidden_size=hidden, intermediate_size=2 * hidden,
        num_hidden_layers=2, mixer_types=(SPARSE, LIGHTNING),
        num_attention_heads=16, num_key_value_heads=2, lightning_nh=2,
        lightning_nkv=2, residual_depth=32,
        sparse=SparseSpec(topk=3, window_size=64, dense_len=256))
    model = MiniCPMSALAForCausalLM(cfg)
    model.eval()
    eng = LLMEngine(model, page_size=page, prefill_chunk=128, max_batch_slots=4,
                    max_seq_len=(doc_pages + 2) * page, prefix_cache=True,
                    state_checkpoints=2)
    log(f"state: warmup took {eng.warmup():.1f}s")
    rng = np.random.default_rng(1)
    doc = rng.integers(0, cfg.vocab_size, doc_pages * page, dtype=np.int32)
    asks = [np.concatenate([doc, rng.integers(0, cfg.vocab_size, n, dtype=np.int32)])
            for n in (40, 90)]
    eng.start()
    try:
        eng.submit(doc, max_new_tokens=2).result(timeout=timeout)
        outs = [f.result(timeout=timeout) for f in
                [eng.submit(p, max_new_tokens=new_tokens) for p in asks]]
    finally:
        eng.stop()
    st = eng.stats()
    ck = st["recurrent_state"]["checkpoints"]
    check(ck["stored"] >= 1 and ck["restored"] == 2
          and st["prefix_cache"]["hit_tokens"] == 2 * doc.size,
          f"two questions resumed from the document's state checkpoint: {ck}")
    sp = st["sparse_attention"]["decode"]
    check(sp["layer_calls"] > 0 and sp["selected_blocks"] == 3 * sp["layer_calls"],
          f"every decode query past dense_len read its 3 selected blocks: {sp}")
    worst = _one_pass_gap(model, asks, outs)
    _ran_ahead(eng)
    return worst


# ------------------------------------------------------------------ latent
def latent_phase(doc_tokens=300, new_tokens=8, timeout=600.0):
    """Latent attention and gated experts through the engine, the prefix
    cache on: two questions of one document map its pages (the second page
    and a half of it whole pages, the rest a partial page forked copy-on-
    write), decode takes the latent KERNEL (not the gathered pass), and the
    tokens it gave lie at the top of the model's own one-pass logits (the
    chunk path: no decode kernel)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.llm_server import _attn_dispatch_series
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)

    paddle.seed(0)
    page = 128
    cfg = DeepseekV3Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=8, n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=1024)
    model = DeepseekV3ForCausalLM(cfg)
    model.eval()
    taken = lambda: {labels[0]: n for labels, n in _attn_dispatch_series()  # noqa: E731
                     if labels[0].startswith("latent_")}
    before = taken()
    eng = LLMEngine(model, page_size=page, prefill_chunk=128, max_batch_slots=4,
                    max_seq_len=5 * page, prefix_cache=True)
    log(f"latent: warmup took {eng.warmup():.1f}s")
    rng = np.random.default_rng(1)
    doc = rng.integers(0, cfg.vocab_size, doc_tokens, dtype=np.int32)
    asks = [np.concatenate([doc, rng.integers(0, cfg.vocab_size, n, dtype=np.int32)])
            for n in (40, 90)]
    eng.start()
    try:
        eng.submit(doc, max_new_tokens=2).result(timeout=timeout)
        outs = [f.result(timeout=timeout) for f in
                [eng.submit(p, max_new_tokens=new_tokens) for p in asks]]
    finally:
        eng.stop()
    st = eng.stats()
    after = taken()
    check(after.get("latent_kernel", 0) > before.get("latent_kernel", 0)
          and after.get("latent_dense", 0) == before.get("latent_dense", 0),
          f"decode took the latent kernel, not the gathered pass: {after}")
    pc = st["prefix_cache"]
    check(pc["hit_tokens"] >= 2 * (doc_tokens // page) * page and pc["cow_copies"] >= 1,
          f"two questions mapped the document's latent pages, one forked: {pc}")
    lat, moe = st["latent_attention"]["decode"], st["moe"]["decode"]
    check(lat["layer_calls"] > 0
          and moe["pairs_held"] == lat["layer_calls"] // 2 * 2 and moe["pairs_absent"] == 0,
          f"decode rows were counted by both kinds of layer: {lat} {moe}")
    worst = _one_pass_gap(model, asks, outs)
    _ran_ahead(eng)
    return worst


# ----------------------------------------------------------------- granite
def granite_phase(n_layers=10, new_tokens=8, timeout=600.0, **widths):
    """Granite-4.0-H at its published widths, one period deep (9 Mamba-2
    layers and one attention layer), with the benchmark's seeded weights:
    64-wide heads ride the paged KERNEL two a 128-lane row (not the dense
    pass), the head is the embedding, and the served tokens (decode kernels,
    a prompt of two chunks carrying its state) lie at the top of the model's
    own one-pass logits.  The logits' spread is ~0.013 (the tied head reads
    an embedding drawn at std 0.00225, over logits_scaling 8): a wrong
    kernel serves a token ~0.05 below the best, rounding ~0.001."""
    import json
    import os

    from benchmark import serve_granite
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.llm_server import _attn_dispatch_series

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                           "configs", "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=n_layers,
               layer_types=cfg["layer_types"][:n_layers], **widths)
    model = serve_granite.build_model(cfg, seed=1)
    names = [n for n, _ in model.named_parameters()]
    check("lm_head" not in names and names.count("embed_tokens") == 1,
          "the head is the embedding: one [vocab, hidden] leaf")
    taken = lambda: {labels[0]: n for labels, n in _attn_dispatch_series()  # noqa: E731
                     if labels[0].startswith("paged_")}
    before = taken()
    eng = LLMEngine(model, page_size=128, prefill_chunk=128, max_batch_slots=4,
                    max_seq_len=256)
    log(f"granite: warmup took {eng.warmup():.1f}s")
    rng = np.random.default_rng(2)
    asks = [rng.integers(0, cfg["vocab_size"], n, dtype=np.int32) for n in (100, 200)]
    eng.start()
    try:
        outs = [f.result(timeout=timeout) for f in
                [eng.submit(p, max_new_tokens=new_tokens) for p in asks]]
    finally:
        eng.stop()
    after = taken()
    check(after.get("paged_kernel", 0) > before.get("paged_kernel", 0)
          and after.get("paged_dense", 0) == before.get("paged_dense", 0),
          f"64-wide heads took the paged kernel, not the dense pass: {after}")
    rs = eng.stats()["recurrent_state"]
    check(rs["carried_chunks"] == 1, f"the 200-token prompt carried its state "
          f"into its second chunk: {rs['carried_chunks']}")
    worst = _one_pass_gap(model, asks, outs, tol=0.01)
    _ran_ahead(eng)
    return worst


# ------------------------------------------------------------- granite_moe
def granite_moe_phase(held=8, new_tokens=8, timeout=600.0, **widths):
    """Granite-4.0-H-Small's layer at its published widths, two layers
    (mamba, attention) each with `held` of the router's 72 experts, with the
    benchmark's seeded weights: the served tokens (decode kernels, a prompt
    of two chunks carrying its state) lie at the top of the float32
    reference's logits, and the expert counters count pairs through the
    recurrent AND the paged layer.  Returns the widest gap."""
    import json
    import os

    from benchmark import serve_granite_moe
    from benchmark.reference import granite_moe_ref
    from paddle_tpu.inference import LLMEngine

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                           "configs", "granite-4.0-h-small-l10-ep2.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=2, layer_types=["mamba", "attention"],
               num_local_experts=held,
               share=dict(cfg["share"], experts_held=[0, held]), **widths)
    model = serve_granite_moe.build_model(cfg, seed=1)
    eng = LLMEngine(model, page_size=128, prefill_chunk=128, max_batch_slots=4,
                    max_seq_len=256)
    log(f"granite_moe: warmup took {eng.warmup():.1f}s")
    rng = np.random.default_rng(3)
    asks = [rng.integers(0, cfg["vocab_size"], n, dtype=np.int32) for n in (100, 200)]
    eng.start()
    try:
        outs = [f.result(timeout=timeout) for f in
                [eng.submit(p, max_new_tokens=new_tokens) for p in asks]]
    finally:
        eng.stop()
    moe = eng.stats()["moe"]
    check(moe["decode"]["layer_calls"] % 2 == 0 and moe["decode"]["pairs_held"] > 0
          and moe["prefill"]["layer_calls"] == 3 * 2,
          f"both layers counted their pairs (stats()['moe']): {moe}")
    del eng, model
    _free()
    gaps, _ = granite_moe_ref.served_gap(
        cfg, 1, [(p, np.asarray(o, np.int32)) for p, o in zip(asks, outs)], 256)
    worst = float(np.max(gaps))
    check(worst < 0.01, f"served tokens lie within {worst:.4f} (< 0.01) of the "
          "float32 reference's best")
    return worst


# ----------------------------------------------------------------- profile
def profile_phase(n_layers=2, seconds=1.0, n_requests=16, new_tokens=500,
                  timeout=600.0, **overrides):
    """The engine accounts for its device time: a census of its compiled
    programs (built here, on demand) and a profile of the running pump,
    reduced to seconds by (program, named scope)."""
    from paddle_tpu.inference import LLMEngine

    cfg, model = _llama(n_layers, tensor_parallel=False, **overrides)
    model.eval()
    eng = LLMEngine(model, kv_layout="paged", page_size=128, prefill_chunk=256,
                    max_seq_len=1024, max_batch_slots=8)
    log(f"profile: {n_layers} layers, warmup took {eng.warmup():.1f}s")
    t = time.perf_counter()
    census = eng.program_census()
    log(f"profile: census of {sorted(census)} built in "
        f"{time.perf_counter() - t:.1f}s "
        f"({sum(map(len, census.values()))} rows)")
    rng = np.random.default_rng(2)
    eng.start()
    try:
        futures = [eng.submit(rng.integers(0, cfg.vocab_size, 300, dtype=np.int32),
                              max_new_tokens=new_tokens)
                   for _ in range(n_requests)]
        time.sleep(0.5)  # past the prompts' chunks, into steady decoding
        red = eng.profile_device(seconds)
        for f in futures:
            f.result(timeout=timeout)
        device_time = eng.stats()["device_time"]
    finally:
        eng.stop()
    check(red is not None, f"profile_device ran: {device_time}")
    busy = red["busy_s"]
    # a 2-layer engine is paced by its host: about half the window is work
    check(busy > 0.2 * seconds,
          f"the device was busy {busy:.3f}s of the {red['window_s']:.3f}s profiled")
    check(set(red["programs"]) <= set(census) and "jit_llm_decode" in red["programs"],
          f"every program of the dump is the census's {sorted(red['programs'])} "
          f"or listed as other {sorted(red['other_programs'])}")
    check(not red["other_programs"],
          "no program but the engine's ran in the window")
    check(red["unmatched_s"] <= 0.01 * busy,
          f"{red['unmatched_s'] / busy:.3%} of the busy time has no census row "
          f"({red['unmatched']})")
    named = sum(v["seconds"] for p in red["programs"].values()
                for v in p["scopes"].values())
    check(abs(named + red["unmatched_s"] - busy) <= 0.005 * busy,
          f"self time adds up: {named + red['unmatched_s']:.4f}s of {busy:.4f}s busy")
    log(f"profile: device seconds by program and scope: {device_time['seconds']}; "
        f"the profiler's seconds: {device_time['profiler_s']}")
    return device_time["seconds"]


# ------------------------------------------------------------------ kernel
#: max |kernel - dense| allowed, outputs O(1).  Both paths round the
#: probabilities to bf16 before the PV matmul (relative 2^-9) and emit bf16;
#: the dense path also rounds the scores to bf16, the kernel keeps them f32.
#: A score error of 2^-9 * |s| at |s| <= ~5 moves a probability by ~1%, and
#: outputs are probability-weighted means of N(0,1) values: ~1e-2 worst
#: case over a few thousand outputs.  int8 adds the dense path's bf16
#: rounding of (int8 * scale) per key, a second ~2^-9 relative score error.
KERNEL_TOL = {"bf16": 3e-2, "int8": 5e-2}


def kernel_phase(heads, kv_heads, head_dim, page, slots, num_pages,
                 max_pages, chunk):
    """The ragged paged kernel against the same call forced dense, at the
    engine's own shapes: S=1 decode, one S=chunk prefill chunk at a
    non-zero offset, and the S=5 speculative-verify ladder; then at the chat
    cell's decode shape, and timed at 1 page a slot and at 14."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.kv_cache import _quantize_kv
    from paddle_tpu.ops import decode_attention as da

    rng = np.random.default_rng(1)
    normal = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s, np.float32), jnp.bfloat16)
    kp, vp = (normal(num_pages, kv_heads, page, head_dim) for _ in range(2))
    kq, ks = _quantize_kv(kp)
    vq, vs = _quantize_kv(vp)
    L = max_pages * page
    errs = {}

    def attend(path, q, offs, tbl, pools):
        # the path is chosen at trace time: a fresh jit per call
        da._FORCE_PATH = path
        try:
            out = jax.jit(lambda q, off, tbl, *p: da.paged_decode_attention(
                q, p[0], p[1], off, tbl, *p[2:]))(q, offs, tbl, *pools)
        finally:
            da._FORCE_PATH = None
        return np.asarray(out, np.float32)

    for S, B in ((1, slots), (chunk, 1), (5, slots)):
        # one prefill chunk: the third of its prompt.  A batch: ragged cached
        # lengths — empty, one short of a page, exactly a page (the first
        # query token then lands ONE TOKEN INTO A NEW PAGE), deep, full
        offs = np.array([2 * chunk] if B == 1 else np.resize(
            [0, page - 1, page, 2 * page + 1, L // 2 + 3, L - S - page,
             L - S - 1, L - S], B), np.int32).clip(0, L - S)
        tbl = rng.permutation(np.arange(1, num_pages))[:B * max_pages] \
            .reshape(B, max_pages).astype(np.int32)
        q = normal(B, S, heads, head_dim)
        for name, pools in (("bf16", (kp, vp)), ("int8", (kq, vq, ks, vs))):
            got = attend(None, q, offs, tbl, pools)
            want = attend("dense", q, offs, tbl, pools)
            err = float(np.max(np.abs(got - want)))
            errs[f"S{S}_{name}"] = err
            check(np.isfinite(got).all() and err < KERNEL_TOL[name],
                  f"paged kernel vs dense, S={S} B={B} {name}: max abs err "
                  f"{err:.2e} < {KERNEL_TOL[name]}")

    # the chat cell's decode tick: 32 slots with tables of 32 entries, 12
    # live at 5-6 pages, 20 masked to the trash page at a stale position
    B, M, deep = 32, 32, 14
    kp, vp = (normal(1 + B * deep, kv_heads, page, head_dim) for _ in range(2))
    free = rng.permutation(np.arange(1, 1 + B * deep))
    live = np.arange(B) < 12
    offs = np.where(live, rng.integers(4 * page, 6 * page, B),
                    7 * page + 4).astype(np.int32)
    tbl = np.zeros((B, M), np.int32)
    tbl[live, :6] = free[:12 * 6].reshape(12, 6)
    q = normal(B, 1, heads, head_dim)
    got = attend(None, q, offs, tbl, (kp, vp))
    want = attend("dense", q, offs, tbl, (kp, vp))
    errs["chat_bf16"] = err = float(np.max(np.abs(got - want)[live]))
    check(np.isfinite(got).all() and err < KERNEL_TOL["bf16"]
          and not got[~live].any(),
          f"paged kernel vs dense, 12 of {B} slots live in {M}-entry tables: "
          f"max abs err {err:.2e} < {KERNEL_TOL['bf16']}, masked slots zero")
    # what a call costs follows what the slots hold, not the table's width
    call = jax.jit(da.paged_decode_attention)
    for n in (1, deep):
        tbl = np.zeros((B, M), np.int32)
        tbl[:, :n] = free[:B * n].reshape(B, n)
        args = (q, kp, vp, np.full(B, n * page - 1, np.int32), tbl)
        call(*args).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            out = call(*args)
        out.block_until_ready()
        log(f"kernel: {B} slots x {n} page(s) in {M}-entry tables: "
            f"{(time.perf_counter() - t0) / 20 * 1e3:.3f} ms a call")
    return errs


# ------------------------------------------------------------------- train
def _train_setup(n_layers, tensor_parallel, batch, seq, **overrides):
    import paddle_tpu as paddle

    cfg, model = _llama(n_layers, tensor_parallel,
                        max_position_embeddings=seq, **overrides)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                                 parameters=model.parameters())

    def loss_fn(ids, labels):
        return paddle.nn.functional.cross_entropy(
            model(ids).reshape([-1, cfg.vocab_size]), labels.reshape([-1]))

    rng = np.random.default_rng(2)
    ids, labels = (paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32))
        for _ in range(2))
    return cfg, model, opt, loss_fn, ids, labels


def _check_losses(tag, losses, vocab):
    check(all(math.isfinite(x) for x in losses) and losses[-1] < losses[0],
          f"{tag}: losses {[round(x, 4) for x in losses]} finite and falling "
          f"(ln vocab = {math.log(vocab):.3f})")


def _check_flash_in_hlo(tag, hlo):
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    found = {k for k in ("flash_fwd", "flash_dq", "flash_dkv")
             if any(k in ln for ln in calls)}
    check(len(found) == 3, f"{tag}: compiled step holds the Mosaic flash "
          f"kernels {sorted(found)} ({len(calls)} custom calls)")


def train_phase(n_layers=N_LAYERS, batch=4, seq=SEQ, steps=3, **overrides):
    """bench.py's _bench_llama_h4096 step: TrainStep + AdamW, one repeated
    seeded batch.  Returns the losses."""
    import paddle_tpu as paddle

    cfg, model, opt, loss_fn, ids, labels = _train_setup(
        n_layers, False, batch, seq, **overrides)
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    log(f"train: {n_layers} layers, batch {batch} x seq {seq}, compiling ...")
    losses = [float(step(ids, labels).item()) for _ in range(steps)]
    _check_losses("train", losses, cfg.vocab_size)
    # the same program again, ahead of time, for its text (a persistent-
    # cache hit when enable_compile_cache() is on)
    import jax.numpy as jnp

    from paddle_tpu.framework import random as _random

    params, buffers = model.functional_state()
    hlo = step._jitted.lower(
        params, buffers, step._opt_state, step._scaler_state,
        jnp.asarray(opt.get_lr(), jnp.float32), _random.get_rng_key(),
        ids._value, labels._value).compile().as_text()
    _check_flash_in_hlo("train", hlo)
    return losses


# -------------------------------------------------------------------- mesh
def _check_memory(devs):
    """Parameters are built on device 0 and only then placed: report every
    device, and device 0's peak."""
    mem = [d.memory_stats() for d in devs]
    log("mesh: bytes_in_use " + ", ".join(
        f"dev{d.id}={m['bytes_in_use'] / 2**20:.0f}MiB "
        f"(peak {m['peak_bytes_in_use'] / 2**20:.0f})"
        for d, m in zip(devs, mem)))
    check(min(m["bytes_in_use"] for m in mem) > 2**20,
          "mesh: every device holds state")


def mesh_phase(ref_loss, n_layers=N_LAYERS, batch=4, seq=SEQ, steps=3,
               **overrides):
    """ShardedTrainStep(zero_stage=2) over sharding=2 x mp=2: the flash
    kernel must survive a real mesh (sharding_ctx.shard_kernel)."""
    import jax

    from paddle_tpu.distributed import ShardedTrainStep, build_mesh

    if len(jax.devices()) < 4:
        log(f"mesh: skipped, {len(jax.devices())} device")
        return None
    mesh = build_mesh(sharding=2, mp=2)
    devs = list(mesh.devices.flat)
    log(f"mesh: {dict(mesh.shape)} over device ids {[d.id for d in devs]}")
    cfg, model, opt, loss_fn, ids, labels = _train_setup(
        n_layers, True, batch, seq, **overrides)
    step = ShardedTrainStep(model, loss_fn, opt, mesh, zero_stage=2)
    losses = [float(step(ids, labels).item()) for _ in range(steps)]
    _check_losses("mesh", losses, cfg.vocab_size)
    check(abs(losses[0] - ref_loss) < 0.1,
          f"mesh: step-1 loss {losses[0]:.4f} within 0.1 of the one-chip "
          f"{ref_loss:.4f}")
    _check_memory(devs)
    hlo = step._compile_for_analysis(ids, labels).as_text()
    _check_flash_in_hlo("mesh", hlo)
    return losses


def main():
    dev = device_gate()
    import paddle_tpu
    from paddle_tpu.core import native
    from paddle_tpu.core.device import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    geom = serve_phase()
    _free()
    errs = kernel_phase(**geom)
    _free()
    state_gap = state_phase()
    _free()
    latent_gap = latent_phase()
    _free()
    granite_gap = granite_phase()
    _free()
    granite_moe_gap = granite_moe_phase()
    _free()
    profile_phase()
    _free()
    losses = train_phase()
    _free()
    mesh_losses = mesh_phase(losses[0])
    # built_this_run=False with AVAILABLE=True means a library that was
    # already on disk was loaded: not built from this checkout's sources
    log(f"native: AVAILABLE={native.AVAILABLE} "
        f"built_this_run={native.BUILT_THIS_RUN}")
    log(f"summary: layers={N_LAYERS} kernel_err={errs} state_gap={state_gap:.4f} "
        f"latent_gap={latent_gap:.4f} granite_gap={granite_gap:.4f} "
        f"granite_moe_gap={granite_moe_gap:.4f} train={losses} "
        f"mesh={mesh_losses} paddle_tpu={paddle_tpu.__version__} "
        f"wall={time.perf_counter() - T0:.0f}s")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
