"""A served cell: a start()ed `LLMEngine` fed only through `submit()`.

Set-up builds the model at the configuration's sizes, hands it the seeded
weights, warms the engine's programs and (where the traffic shares prefixes)
sends each prefix once.  The window offers the traffic of
benchmark/loadgen.py; per request the benchmark keeps when it was due, when
it was submitted, when its first token existed (the close of the engine's
first `admission` span: the engine has no per-token hook) and when its future
resolved.  After the window every request is waited for; `correct` compares a
sample of what was served with the plain reference.
"""
from __future__ import annotations

import gc
import math
import queue
import threading
import time

import numpy as np

from benchmark import loadgen, weights

DRAIN_SECONDS = 60.0  # how long past the close a request is waited for


def percentile(values, q, missing=0, late=math.inf):
    """Nearest-rank q-quantile of `values` plus `missing` requests that never
    answered and count as `late`."""
    n = len(values) + missing
    if n == 0:
        return None
    k = max(0, math.ceil(q * n) - 1)
    vals = sorted(values)
    return vals[k] if k < len(vals) else late


def registry_snapshot():
    """{family: {"value"|"count"|"sum": total over its series}}."""
    from paddle_tpu.observability import metrics

    snap = {}
    for name in metrics.REGISTRY.names():
        fam = metrics.REGISTRY.get(name)
        tot = {}
        for _, child in fam.series():
            for field in ("value", "count", "sum"):
                v = getattr(child, field, None)
                if isinstance(v, (int, float)):
                    tot[field] = tot.get(field, 0.0) + v
        snap[name] = tot
    return snap


def _flatten(d, prefix=""):
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_flatten(v, f"{prefix}{k}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[prefix + k] = v
    return out


def build_engine(cfg, job, seed, store_capacity):
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability.tracing import Tracer, TraceStore

    paddle.seed(0)
    lc = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        # the rope table is built to what the engine can hold, not to the
        # source's max_position_embeddings
        max_position_embeddings=job["engine"]["max_seq_len"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], tensor_parallel=False)
    model = LlamaForCausalLM(lc)
    if cfg["torch_dtype"] == "bfloat16":
        model = model.bfloat16()
    model.eval()
    weights.load_into(model, cfg, seed)
    # every trace is kept: the per-request times come from their spans
    tracer = Tracer(store=TraceStore(capacity=store_capacity, sample_every=1))
    eng = LLMEngine(model, kv_layout="paged", tracer=tracer, **job["engine"])
    return model, eng, tracer


class _Recorder:
    """Submits requests and keeps the benchmark's own clock per request."""

    def __init__(self, eng, tag):
        self.eng, self.tag = eng, tag
        self.records = []
        self.done_q = queue.Queue()

    def submit(self, req, due_abs):
        import jax

        rec = {"id": req["id"], "trace_id": f"{self.tag}-{req['id']}",
               "due": due_abs, "prompt": req["prompt"],
               "max_new_tokens": req["max_new_tokens"], "prefix": req["prefix"],
               "done": None, "tokens": None, "error": None}
        with jax.profiler.TraceAnnotation("bench_submit"):
            rec["submit"] = time.perf_counter()
            if rec["due"] is None:
                rec["due"] = rec["submit"]
            try:
                fut = self.eng.submit(req["prompt"],
                                      max_new_tokens=req["max_new_tokens"],
                                      trace_id=rec["trace_id"])
            except Exception as e:  # shed or refused: a failed request
                rec["error"] = repr(e)
                rec["done"] = time.perf_counter()
                self.records.append(rec)
                self.done_q.put(rec)
                return rec
        self.records.append(rec)
        fut.add_done_callback(lambda f, r=rec: self._done(f, r))
        return rec

    def _done(self, fut, rec):
        rec["done"] = time.perf_counter()
        try:
            rec["tokens"] = np.asarray(fut.result(), np.int32)
        except Exception as e:
            rec["error"] = repr(e)
        self.done_q.put(rec)

    def wait_all(self, deadline):
        """True if every record resolved before `deadline`."""
        while any(r["done"] is None for r in self.records):
            left = deadline - time.perf_counter()
            if left <= 0:
                return False
            try:
                self.done_q.get(timeout=min(left, 0.5))
            except queue.Empty:
                pass
        return True


def _attach_spans(rec, store):
    """First-token time, queue wait, cached tokens and prefill chunks of one
    request, from its trace; times made absolute from the submit instant."""
    tr = store.get_trace(rec["trace_id"])
    rec.update(first_token=None, queue_wait=None, cached=0, chunks=[])
    if tr is None:
        return
    t0 = rec["submit"]
    for sp in tr.find_spans("admission"):
        if sp.error is None and sp.duration_s is not None:
            rec["first_token"] = t0 + sp.start_s + sp.duration_s
            rec["cached"] = int(sp.attrs.get("cached_tokens", 0))
            break
    qw = tr.find_spans("queue_wait")
    if qw:
        rec["queue_wait"] = qw[0].duration_s
    for sp in tr.find_spans("llm_prefill_chunk"):
        rec["chunks"].append((t0 + sp.start_s, t0 + sp.start_s + (sp.duration_s or 0.0),
                              int(sp.attrs.get("tokens", 0))))


def work_between(records, ta, tb):
    """What the engine had to compute in [ta, tb), from the requests' own
    lengths: prefill chunks whose call began inside it, as (offset, queries),
    and the decode tokens of every request, spread evenly from its first
    token to its completion, with the contexts they attended."""
    chunks, dec_tokens, dec_ctx, head_rows = [], 0.0, 0.0, 0
    for r in records:
        off = r.get("cached", 0)
        for a, _, m in r.get("chunks", ()):
            if ta <= a < tb:
                chunks.append((off, m))
                head_rows += 1
            off += m
        if r.get("first_token") is None or r["done"] is None or r["tokens"] is None:
            continue
        n_dec = len(r["tokens"]) - 1
        a, b = r["first_token"], r["done"]
        if n_dec <= 0 or b <= a:
            continue
        lo, hi = max(a, ta), min(b, tb)
        if hi <= lo:
            continue
        f0, f1 = (lo - a) / (b - a), (hi - a) / (b - a)
        toks = (f1 - f0) * n_dec
        n = len(r["prompt"])
        # decode token j (1-based) attends n + j keys
        dec_tokens += toks
        dec_ctx += toks * (n + (f0 + f1) / 2 * n_dec + 0.5)
    return {"chunks": chunks, "decode_tokens": dec_tokens,
            "decode_ctx_sum": dec_ctx,
            "prefill_tokens": sum(m for _, m in chunks),
            # a chunk's query i (0-based) at offset o attends o + i + 1 keys
            "prefill_pairs": sum(m * o + m * (m + 1) // 2 for o, m in chunks),
            "head_rows": head_rows + dec_tokens}


def _warm_up(eng, cfg, prefixes, seed, limit):
    """Set-up traffic: each shared prefix once (fills the prefix cache), or two
    short requests, so the pump has run every path it will."""
    rng = np.random.default_rng([int(seed), 1])
    tail = lambda n: rng.integers(0, cfg["vocab_size"], n, dtype=np.int32)  # noqa: E731
    prompts = [np.concatenate([p, tail(8)]) for p in prefixes] \
        or [tail(min(300, limit // 2)) for _ in range(2)]
    rec = _Recorder(eng, "w")
    for i, p in enumerate(prompts):
        rec.submit({"id": i, "prompt": p, "max_new_tokens": 4, "prefix": None}, None)
    if not rec.wait_all(time.perf_counter() + 300) \
            or any(r["error"] for r in rec.records):
        raise RuntimeError(f"set-up requests failed: {[r['error'] for r in rec.records]}")


def _offer_open(rec, reqs, t0, t1):
    """Each request at its due time, whether or not earlier ones finished."""
    for r in reqs:
        due = t0 + r["due"]
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        rec.submit(r, due)
    time.sleep(max(0.0, t1 - time.perf_counter()))


def _offer_closed(rec, reqs, clients, t1):
    """`clients` callers: the next request goes out when one returns."""
    it = iter(reqs)
    for _ in range(clients):
        rec.submit(next(it), None)
    while True:
        left = t1 - time.perf_counter()
        if left <= 0:
            return
        try:
            rec.done_q.get(timeout=left)
        except queue.Empty:
            return
        nxt = next(it, None)
        if nxt is None:
            raise RuntimeError("closed loop ran out of prepared requests: raise `pool`")
        rec.submit(nxt, None)


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
    log(f"engine built: {weights.n_params(cfg) / 1e9:.2f} B parameters")
    log(f"warmup() took {eng.warmup():.1f} s")
    eng.start()
    try:
        _warm_up(eng, cfg, prefixes, seed, limit)
        rec = _Recorder(eng, "r")
        gc.collect()
        gc.freeze()  # the model's objects never die: keep the collector off them

        snapshot = lambda: {"registry": registry_snapshot(),  # noqa: E731
                            "stats": _flatten(eng.stats())}
        tracer_thread = None
        if trace:
            tracer_thread = _TraceWindow(job["trace_seconds"], seconds, snapshot)
        before = snapshot()
        t0 = time.perf_counter()
        setup_s = t0 - clock0
        t1 = t0 + seconds
        if tracer_thread:
            tracer_thread.start(t0)
        if traffic["loop"] == "open":
            _offer_open(rec, reqs, t0, t1)
        else:
            _offer_closed(rec, reqs, traffic["clients"], t1)
        t_close = time.perf_counter()
        after = snapshot()
        drained = rec.wait_all(t_close + DRAIN_SECONDS)
        t_gave_up = time.perf_counter()
        red = tracer_thread.finish() if tracer_thread else None
    finally:
        eng.stop()
    devs = jax.local_devices()[:cell["cell"]["chips"]]
    mem = [d.memory_stats() or {} for d in devs]
    records = rec.records
    for r in records:
        _attach_spans(r, tracer.store)
    log(f"window closed: {len(records)} submitted, drained={drained}")

    # --- end-to-end metrics ------------------------------------------------
    ok = [r for r in records if r["error"] is None and r["done"] is not None
          and r["tokens"] is not None and r["first_token"] is not None]
    failed = len(records) - len(ok)
    # a request that failed or never answered was given up on this late
    late = t_gave_up - t0
    ttft = [r["first_token"] - r["due"] for r in ok]
    tpot = [(r["done"] - r["first_token"]) / (len(r["tokens"]) - 1)
            for r in ok if len(r["tokens"]) >= 2]
    in_window = [r for r in ok if r["done"] <= t1]
    e2e = {
        "setup_s": setup_s,
        "ttft_p95_ms": _ms(percentile(ttft, 0.95, failed, late)),
        "tpot_p95_ms": _ms(percentile(tpot, 0.95, failed, late)),
        "out_tokens_per_s": sum(len(r["tokens"]) for r in in_window) / seconds,
    }
    obs = {"kind": "serve", "cfg": cfg, "traffic": traffic,
           "window": (t0, t1), "window_s": seconds, "before": before,
           "after": after, "ok": ok, "trace": red,
           "traced": None,
           "work": lambda a, b: work_between(records, a, b),
           "memory_peak_bytes": max(m.get("peak_bytes_in_use", 0) for m in mem)}
    if tracer_thread:
        # starting and stopping the profiler stalls the host, so a traced
        # run reads its counters, spans and clocks over the part of the
        # window BEFORE the profiler starts, and the device over the traced part
        t_split = tracer_thread.split
        obs.update(window=(t0, t_split), window_s=t_split - t0,
                   after=tracer_thread.snapshot, traced=tracer_thread.bounds,
                   ok=[r for r in ok if r["due"] < t_split])

    # --- free the program, then the reference ----------------------------
    sample = _sample(ok, job["check_requests"], seed)
    del eng, model, tracer, rec
    gc.unfreeze()
    gc.collect()
    check = compare(cfg, seed, sample, job["check_pad_to"], job["limits"])
    check["attempted"], check["failed"] = len(records), failed
    check["extra"] = {
        "drain_s": t_gave_up - t_close, "done_in_window": len(in_window),
        "queue_depth_at_close": after["stats"].get("queue_depth"),
        "active_slots_at_close": after["stats"].get("active_slots"),
        "ttft_p50_ms": _ms(percentile(ttft, 0.5, failed, late)),
        "bytes_in_use_at_close": max(m.get("bytes_in_use", 0) for m in mem),
    }
    if "control_widest_logit_gap" in check:  # only a control run has it
        check["extra"]["control_widest_logit_gap"] = check["control_widest_logit_gap"]
    return e2e, obs, check


def _ms(x):
    return None if x is None else x * 1e3


def _sample(ok, n, seed):
    """`n` finished requests drawn from the seed, the longest among them."""
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(cfg, seed, sample, pad_to, limits, quant=None):
    """The numbers `correct` is decided on, each beside its limit."""
    from benchmark.reference import llama_ref

    if not sample:
        return {"correct": False, "numbers": {"sampled_requests": [0, ">=1"]}}
    pairs = [(r["prompt"], r["tokens"]) for r in sample]
    gaps, control = llama_ref.served_gap(cfg, seed, pairs, pad_to, quant)
    vocab_ok = all(0 <= int(t) < cfg["vocab_size"] for _, o in pairs for t in o)
    length_ok = all(len(r["tokens"]) == r["max_new_tokens"] for r in sample)
    widest = float(np.max(gaps))
    numbers = {
        "widest_logit_gap": [widest, limits["widest_logit_gap"]],
        "served_tokens_checked": [int(len(gaps)), ">=1"],
        "tokens_in_vocab": [int(vocab_ok), 1],
        "lengths_as_asked": [int(length_ok), 1],
    }
    out = {"correct": bool(widest <= limits["widest_logit_gap"] and vocab_ok
                           and length_ok and np.isfinite(widest)),
           "numbers": numbers}
    if control is not None:
        out["control_widest_logit_gap"] = float(np.max(control))
    return out


class _TraceWindow:
    """Profiles the last `length` seconds of the window on a thread of its
    own.  `split` is the instant before the profiler starts: the snapshot of
    the counters is taken there."""

    STALL = 2.0  # allowed for start_trace before the traced part begins

    def __init__(self, length, seconds, snapshot_fn):
        self.length = min(length, seconds / 3)
        self.offset = max(seconds - self.length - self.STALL, seconds / 3)
        self.snapshot_fn = snapshot_fn
        self.dir = self.bounds = self.split = self.snapshot = None
        self._thread = None
        self._error = None

    def start(self, t0):
        import tempfile

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")  # under TMPDIR
        self._thread = threading.Thread(target=self._run, args=(t0,), daemon=True)
        self._thread.start()

    def _run(self, t0):
        import jax

        try:
            time.sleep(max(0.0, t0 + self.offset - time.perf_counter()))
            self.snapshot = self.snapshot_fn()
            self.split = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python frames cost ~100k events a second
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench_trace_window"):
                    time.sleep(self.length)
                self.bounds = (a, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported by finish()
            self._error = e

    def finish(self):
        import shutil

        from benchmark import reduce_trace

        self._thread.join()
        if self._error is not None:
            raise self._error
        try:
            return reduce_trace.reduce(
                reduce_trace.load(reduce_trace.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
