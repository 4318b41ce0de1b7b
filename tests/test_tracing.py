"""Request-scoped tracing (observability/tracing.py, ISSUE 8).

Oracles: the span TREE of a served request is exact and deterministic
(names + nesting, including both admission episodes of a page-preempted
request); a histogram bucket's exemplar trace_id resolves to a stored
trace on /tracez; parse_prometheus(render_prometheus()) round-trips
exemplars; tail sampling keeps exactly the error/preempted/SLO-violating
traces plus a deterministic 1-in-N of the rest; the disabled fast path
stays within the bench overhead budget of a no-tracing baseline.
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed import fault_tolerance as ft
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import flight_recorder as obs_flight
from paddle_tpu.observability import scrape as obs_scrape
from paddle_tpu.observability import tracing
from paddle_tpu.observability.metrics import MetricRegistry
from paddle_tpu.testing import faults

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _tracer(sample_every=1, capacity=64):
    return tracing.Tracer(store=tracing.TraceStore(
        capacity=capacity, sample_every=sample_every))


def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# ------------------------------------------------------------- trace object
def test_span_tree_structure_and_attrs():
    tr = _tracer()
    t = tr.start_trace("op", prompt_tokens=9)
    with t.span("outer", k=1):
        with t.span("inner"):
            pass
        t.add_span("measured", duration_s=0.25, ticks=3)
    t.end("ok", done=True)
    assert t.span_tree() == [["outer", [["inner", []], ["measured", []]]]]
    d = t.to_dict()
    assert d["status"] == "ok"
    assert d["attrs"] == {"prompt_tokens": 9, "done": True}
    outer = d["spans"][0]
    assert outer["attrs"] == {"k": 1}
    measured = outer["children"][1]
    assert measured["duration_s"] == 0.25
    assert t.root.span_count() - 1 == 3
    # chrome export covers every span
    names = [e["name"] for e in t.to_chrome_trace()["traceEvents"]]
    assert names == ["op", "outer", "inner", "measured"]


def test_span_error_and_dangling_close():
    tr = _tracer()
    t = tr.start_trace("op")
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    left_open = t.span("left_open").open()  # noqa: F841 -- ended by end()
    t.end("error", error="x")
    spans = {s["name"]: s for s in t.to_dict()["spans"]}
    assert "RuntimeError" in spans["boom"]["error"]
    assert spans["left_open"]["duration_s"] is not None  # end() closed it
    # end() is idempotent: a second end must not re-offer to the store
    n = tr.store.stats()["sampled"]
    t.end("ok")
    assert t.status == "error" and tr.store.stats()["sampled"] == n


def test_disabled_fast_path_returns_null_trace():
    obs.disable()
    try:
        t = tracing.start_trace("op")
        assert t is tracing.NULL_TRACE and not t
        with t.span("a"):
            pass
        t.add_span("b", duration_s=1.0)
        t.mark_slo("s")
        t.end("error")
        assert t.to_dict() == {} and t.trace_id == ""
    finally:
        obs.enable()


def test_disabled_overhead_within_budget():
    """The bench guard's acceptance shape: the disabled lifecycle must sit
    within a small per-request budget of the no-tracing baseline."""
    import bench

    out = bench._bench_tracing(False)
    overhead = out["trace_overhead_us_per_request_disabled"] \
        - out["trace_overhead_us_per_request_baseline"]
    assert overhead < 100.0, out  # generous for CI noise; steady ~5us


# ------------------------------------------------------------ tail sampling
def test_tail_sampling_policy_deterministic():
    store = tracing.TraceStore(capacity=8, sample_every=4)
    tr = tracing.Tracer(store=store)

    def mk(status="ok", **attrs):
        t = tr.start_trace("op", **attrs)
        t.end(status)
        return t

    assert store.offer is not None
    errors = [mk("shed"), mk("expired"), mk("error")]
    assert all(t.sampled_reason == "error" for t in errors)
    pre = mk("ok", preempt_requeues=2)
    assert pre.sampled_reason == "preempted"
    slo_t = tr.start_trace("op")
    slo_t.mark_slo("llm_ttft")
    slo_t.end("ok")
    assert slo_t.sampled_reason == "slo"
    # deterministic 1-in-4 of the healthy rest
    healthy = [mk("ok") for _ in range(8)]
    reasons = [t.sampled_reason for t in healthy]
    assert reasons == [None, None, None, "tail"] * 2
    st = store.stats()
    assert st["sampled"] == 7 and st["dropped"] == 6
    # bounded: capacity 8 evicts oldest
    for _ in range(4):
        mk("shed")
    st = store.stats()
    assert st["stored"] == 8 and st["evicted"] == 3
    assert store.get_trace(errors[0].trace_id) is None  # evicted oldest


# ---------------------------------------------------------------- exemplars
def test_histogram_exemplar_worst_per_bucket_and_roundtrip():
    r = MetricRegistry()
    h = r.histogram("ex_lat_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.03, exemplar="small")
    h.observe(0.07, exemplar="worst-in-bucket")
    h.observe(0.05, exemplar="not-worse")   # 0.05 < 0.07: not retained
    h.observe(0.5)                          # no exemplar: bucket 1.0 bare
    h.observe(7.0, exemplar="overflow")     # +Inf bucket
    text = r.render_prometheus()
    assert '# {trace_id="worst-in-bucket"} 0.07' in text
    assert "not-worse" not in text and "small" not in text
    assert '+Inf"} 5 # {trace_id="overflow"} 7' in text
    snap = r.snapshot()
    ex = snap["ex_lat_seconds"]["series"][0]["exemplars"]
    assert ex["0.1"] == {"labels": {"trace_id": "worst-in-bucket"},
                         "value": 0.07}
    assert "1" not in ex  # bare observation adds no exemplar
    # the acceptance round trip: parse(render()) == snapshot(), exemplars
    # included
    assert obs_scrape.parse_prometheus(text) == snap
    # SampleSet harvests the trace ids for alert correlation
    ss = obs_scrape.SampleSet().add_families(
        obs_scrape.parse_prometheus(text))
    assert ss.exemplar_trace_ids("ex_lat") == ["worst-in-bucket",
                                               "overflow"]


def test_metrics_exemplar_content_negotiation():
    """Exemplars are illegal in the classic text/plain;version=0.0.4
    format: /metrics only emits them for a scraper whose Accept header
    negotiates OpenMetrics (the built-in Scraper does)."""
    from paddle_tpu.observability import exporter as obs_exporter

    reg = MetricRegistry()
    h = reg.histogram("neg_lat_seconds", "l", buckets=(1.0,))
    h.observe(0.5, exemplar="t-neg")
    srv = obs.TelemetryServer(port=0, registry=reg).start()
    try:
        plain = urllib.request.urlopen(srv.url + "/metrics", timeout=5)
        body = plain.read().decode()
        assert plain.headers.get("Content-Type") \
            == obs_exporter.PROMETHEUS_CONTENT_TYPE
        assert "# {" not in body and "# EOF" not in body
        req = urllib.request.Request(srv.url + "/metrics", headers={
            "Accept": "application/openmetrics-text; version=1.0.0, "
                      "text/plain; version=0.0.4"})
        om = urllib.request.urlopen(req, timeout=5)
        om_body = om.read().decode()
        assert om.headers.get("Content-Type") \
            == obs_exporter.OPENMETRICS_CONTENT_TYPE
        assert '# {trace_id="t-neg"} 0.5' in om_body
        assert om_body.endswith("# EOF\n")
        # both variants parse; the OpenMetrics one recovers the exemplar
        assert "exemplars" not in \
            obs_scrape.parse_prometheus(body)["neg_lat_seconds"]["series"][0]
        assert obs_scrape.parse_prometheus(om_body) == reg.snapshot()
        # the fleet scraper negotiates OpenMetrics and harvests the ids
        ss, results = obs_scrape.Scraper(
            [srv.url.replace("http://", "")]).poll()
        assert results[0].ok
        assert ss.exemplar_trace_ids("neg_lat_seconds") == ["t-neg"]
    finally:
        srv.stop()


def test_exemplar_roundtrip_with_labels_and_escapes():
    r = MetricRegistry()
    h = r.histogram("ex_esc_seconds", "l", labelnames=("op",),
                    buckets=(1.0,))
    h.labels(op='we"ird\\x').observe(0.5, exemplar='t"1\\n')
    text = r.render_prometheus()
    assert obs_scrape.parse_prometheus(text) == r.snapshot()


# --------------------------------------------------- engine lifecycle (e2e)
def test_engine_trace_exact_span_tree_with_preemption_and_tracez(model):
    """Acceptance: a request driven through a prefix-cache hit, chunked
    prefill and a FORCED page preemption yields the exact span tree, is
    fetchable from /tracez, and the TTFT histogram's exemplar trace_id
    resolves to a stored trace."""
    rng = np.random.RandomState(77)
    tracer = _tracer()
    ttft = obs.REGISTRY.get("llm_ttft_seconds")
    # A warms a 32-token page-aligned prefix.  B shares it (cache hit ->
    # first chunk skipped) and crosses its next page boundary at decode
    # tick 3, while C holds the pool's last page and stays UNDER its own
    # boundary -> B's growth finds the pool dry (its shared page pins the
    # cache against eviction) and B preempt-requeues: one request, one
    # trace, through prefix hit + chunked prefill + forced preemption.
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=4, tracer=tracer)  # 3 allocatable pages
    head = rng.randint(0, 1024, 32).astype(np.int32)
    pa = np.concatenate([head, rng.randint(0, 1024, 8).astype(np.int32)])
    fa = eng.submit(pa, max_new_tokens=2)
    eng.run_until_complete()
    assert len(fa.result(timeout=1)) == 2
    ta = tracer.store.get_trace(
        [s["trace_id"] for s in tracer.store.list()
         if s["status"] == "ok"][0])
    # A: clean two-chunk prefill, no cache hit, one decode summary
    assert ta.span_tree() == [
        ["queue_wait", []],
        ["admission", [["llm_prefill_chunk", []], ["llm_prefill_chunk", []]]],
        ["decode", []],
    ]
    adm_a = ta.find_spans("admission")[0]
    assert adm_a.attrs["episode"] == 1 and adm_a.attrs["cached_tokens"] == 0

    pb = np.concatenate([head, rng.randint(0, 1024, 30).astype(np.int32)])
    pc = rng.randint(0, 1024, 28).astype(np.int32)
    fb = eng.submit(pb, max_new_tokens=20)
    fc = eng.submit(pc, max_new_tokens=6)
    eng.run_until_complete()
    assert len(fb.result(timeout=1)) == 20 and len(fc.result(timeout=1)) == 6
    tb = next(tracer.store.get_trace(s["trace_id"])
              for s in tracer.store.list()
              if s["sampled_reason"] == "preempted")
    # the EXACT tree: episode 1 prefills one chunk (32 of 62 tokens came
    # from the cache), two decode ticks coalesce into one summary, the
    # requeued episode 2 re-prefills the grown prompt privately in three
    # chunks, then decodes to completion
    assert tb.span_tree() == [
        ["queue_wait", []],
        ["admission", [["llm_prefill_chunk", []]]],
        ["decode", []],
        ["admission", [["llm_prefill_chunk", []], ["llm_prefill_chunk", []],
                       ["llm_prefill_chunk", []]]],
        ["decode", []],
    ]
    admissions = tb.find_spans("admission")
    assert admissions[0].attrs["episode"] == 1
    assert admissions[0].attrs["cached_tokens"] == 32  # the prefix hit
    assert "requeue_reason" not in admissions[0].attrs
    assert admissions[1].attrs["episode"] == 2
    assert admissions[1].attrs["requeue_reason"] == "page_pool_dry"
    assert tb.root.attrs["preempt_requeues"] == 1
    assert tb.status == "ok"
    decs = tb.find_spans("decode")
    assert sum(d.attrs["tokens"] for d in decs) + 2 == 20  # 2 from prefills

    # the TTFT histogram's exemplars resolve to stored traces
    exem = ttft._solo().exemplars()
    ids = {e["labels"]["trace_id"] for e in exem.values()}
    stored = {s["trace_id"] for s in tracer.store.list()}
    assert ids & stored, (ids, stored)

    # /tracez: list + fetch by id + chrome export
    srv = obs.TelemetryServer(port=0, traces=tracer.store).start()
    try:
        _, body = _get(srv.url + "/tracez")
        doc = json.loads(body)
        assert doc["stats"]["stored"] == len(tracer.store)
        assert any(s["trace_id"] == tb.trace_id for s in doc["traces"])
        _, body = _get(srv.url + f"/tracez?trace_id={tb.trace_id}")
        fetched = json.loads(body)
        assert fetched["trace_id"] == tb.trace_id
        assert [s["name"] for s in fetched["spans"]].count("admission") == 2
        _, body = _get(srv.url + f"/tracez?trace_id={tb.trace_id}"
                                 "&format=chrome")
        chrome = json.loads(body)
        assert chrome["metadata"]["trace_id"] == tb.trace_id
        assert any(e["name"] == "llm_prefill_chunk"
                   for e in chrome["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/tracez?trace_id=nope")
        assert ei.value.code == 404
        # /varz carries the sampler stats
        _, body = _get(srv.url + "/varz")
        assert json.loads(body)["tracing"]["stored"] == len(tracer.store)
    finally:
        srv.stop()


def test_engine_cow_fork_stamped_on_trace(model):
    """A request whose first decode write forks its cache-shared tail
    page (roomy pool: fork, not steal-back) carries the episode in its
    trace attrs."""
    rng = np.random.RandomState(9)
    tracer = _tracer()
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    tracer=tracer)  # default pool: plenty of free pages
    out = eng.generate(rng.randint(0, 1024, 40).astype(np.int32),
                       max_new_tokens=3)
    assert len(out) == 3
    t = tracer.store.get_trace(tracer.store.list()[0]["trace_id"])
    assert t.root.attrs.get("cow_forks", 0) >= 1
    assert eng.stats()["prefix_cache"]["cow_copies"] >= 1


def test_engine_expiry_and_shed_traces(model):
    rng = np.random.RandomState(6)
    tracer = _tracer(sample_every=0)  # only tail-keep rule off: errors kept
    now = {"t": 100.0}
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    max_queue_len=1, clock=lambda: now["t"], tracer=tracer)
    eng.submit(rng.randint(0, 1024, 8).astype(np.int32),
               max_new_tokens=2, timeout=5.0)
    with pytest.raises(Exception):
        eng.submit(rng.randint(0, 1024, 8).astype(np.int32),
                   max_new_tokens=2)  # queue full -> shed
    now["t"] += 10.0
    eng.step()  # expires the queued request
    statuses = sorted((s["status"], s["sampled_reason"])
                      for s in tracer.store.list())
    assert ("shed", "error") in statuses and ("expired", "error") in statuses
    shed = next(tracer.store.get_trace(s["trace_id"])
                for s in tracer.store.list() if s["status"] == "shed")
    assert shed.root.attrs["reason"] == "queue_full"


def test_slo_violation_marks_trace(model):
    rng = np.random.RandomState(8)
    tracer = _tracer(sample_every=0)  # ONLY slo/error traces retained
    now = {"t": 0.0}

    def slow_clock():
        now["t"] += 3.0  # every clock read advances 3s: e2e >> target
        return now["t"]

    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    clock=slow_clock, slo_targets={"e2e": 0.5},
                    tracer=tracer)
    assert len(eng.generate(rng.randint(0, 1024, 8).astype(np.int32),
                            max_new_tokens=2)) == 2
    kept = tracer.store.list()
    assert len(kept) == 1 and kept[0]["sampled_reason"] == "slo"
    assert "llm_e2e" in kept[0]["slo_violations"]


# ------------------------------------------------------------ faults marker
@pytest.mark.faults
def test_preempted_request_one_trace_both_episodes(model):
    """Faults acceptance: a page-preempted + requeued request produces ONE
    trace containing BOTH admission episodes, the second carrying the
    requeue reason attribute."""
    rng = np.random.RandomState(25)
    tracer = _tracer()
    pa = rng.randint(0, 1024, 30).astype(np.int32)
    pb = rng.randint(0, 1024, 30).astype(np.int32)
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=32,
                    num_pages=3, prefix_cache=False, tracer=tracer)
    fa = eng.submit(pa, max_new_tokens=4)
    fb = eng.submit(pb, max_new_tokens=4)
    eng.run_until_complete()
    assert len(fa.result(timeout=1)) == 4 and len(fb.result(timeout=1)) == 4
    preempted = [s for s in tracer.store.list()
                 if s["sampled_reason"] == "preempted"]
    assert len(preempted) == 1  # ONE trace spans both episodes
    t = tracer.store.get_trace(preempted[0]["trace_id"])
    eps = t.find_spans("admission")
    assert [s.attrs["episode"] for s in eps] == [1, 2]
    assert "requeue_reason" not in eps[0].attrs
    assert eps[1].attrs["requeue_reason"] == "page_pool_dry"
    # flight events of the preemption carry the trace id
    evts = [e for e in obs_flight.events()
            if e.get("kind") == "page_preemption"
            and e.get("trace_id") == t.trace_id]
    assert evts, "page_preemption flight event must carry the trace_id"


@pytest.mark.faults
def test_watchdog_crash_dump_flight_events_carry_trace_id(model, tmp_path):
    """Faults acceptance: when the pump dies mid-serve, the black-box dump's
    flight events carry the dying request's trace_id, and the sibling
    traces_*.json holds its (failed) trace."""
    obs_flight.clear()
    tracing.TRACES.clear()  # the engine's default tracer feeds the global
    calls = {"n": 0}        # store, whose sibling dump rides every black box

    def dying_clock():
        calls["n"] += 1
        if calls["n"] >= 4:  # submit + first admission stamps survive
            raise faults.InjectedFault(5, "injected clock failure (EIO)")
        return 100.0

    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    clock=dying_clock,
                    flight_recorder_dir=str(tmp_path / "bb"))
    try:
        eng.start()
        fut = eng.submit(np.arange(1, 25, dtype=np.int32), max_new_tokens=4)
        deadline = time.monotonic() + 30
        while eng._pump_error is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng._pump_error is not None, "pump did not die"
        with pytest.raises(Exception):
            fut.result(timeout=10)
    finally:
        eng.stop()
    # the dying request's trace ended with an error and was retained
    kept = [s for s in tracing.TRACES.list() if s["status"] == "error"]
    assert len(kept) == 1
    tid = kept[0]["trace_id"]
    dumps = [n for n in os.listdir(tmp_path / "bb") if n.endswith(".jsonl")]
    assert len(dumps) == 1
    lines = [json.loads(l) for l in open(tmp_path / "bb" / dumps[0])]
    carried = [l for l in lines[1:] if l.get("trace_id") == tid]
    assert carried, "dump's flight events must carry the dying trace_id"
    assert any(l["kind"] == "span" for l in carried)  # its prefill chunk
    # the sibling trace dump is the per-request black box
    sib = [n for n in os.listdir(tmp_path / "bb")
           if n.startswith("traces_watchdog_trip_")]
    assert len(sib) == 1
    doc = json.load(open(tmp_path / "bb" / sib[0]))
    assert any(t["trace_id"] == tid for t in doc["traces"])


# ------------------------------------------------------- recovery lifecycle
def test_recovery_trace_episodes_and_checkpoint_spans(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path / "ck"), keep=3,
                                 save_interval=2)
    state = {"x": np.zeros(1)}
    check = faults.preemption_schedule(2)
    seen = []
    orig = tracing.TRACES.offer
    tracing.TRACES.offer = lambda t: (seen.append(t), orig(t))[1]
    try:
        report = ft.run_with_recovery(
            lambda step: (check(step), state.update(x=state["x"] + 1)),
            4, mgr, get_state=lambda: {"x": state["x"]},
            set_state=lambda s: state.update(x=np.asarray(s["x"])))
    finally:
        tracing.TRACES.offer = orig
    assert (report["completed"], report["restarts"]) == (4, 1)
    t = next(t for t in seen if t.name == "run_with_recovery")
    assert t.status == "ok" and t.sampled_reason == "preempted"
    assert t.root.attrs["restart_episodes"] == 1
    episodes = t.find_spans("episode")
    assert len(episodes) == 2
    assert episodes[0].attrs["start_step"] == 0
    assert "Preemption" in episodes[0].error
    assert episodes[1].attrs["start_step"] == 2 and episodes[1].error is None
    # checkpoint saves/loads nest inside the run trace
    assert t.find_spans("checkpoint_save")
    restore = t.find_spans("restore")
    assert len(restore) == 1
    assert [c.name for c in restore[0].children] == ["checkpoint_load"]
    assert t.find_spans("steps"), "steps coalesce into summary spans"


def test_recovery_fatal_trace_ends_error(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path / "ck"), keep=2)
    seen = []
    orig = tracing.TRACES.offer
    tracing.TRACES.offer = lambda t: (seen.append(t), orig(t))[1]
    try:
        with pytest.raises(RuntimeError, match="boom"):
            ft.run_with_recovery(
                lambda step: (_ for _ in ()).throw(RuntimeError("boom")),
                3, mgr, get_state=lambda: {"x": np.zeros(1)},
                set_state=lambda s: None, recoverable=())
    finally:
        tracing.TRACES.offer = orig
    t = next(t for t in seen if t.name == "run_with_recovery")
    assert t.status == "error" and "boom" in t.root.attrs["error"]
    ep = t.find_spans("episode")
    assert len(ep) == 1 and "boom" in ep[0].error


# ------------------------------------------------------- alert notify hook
def test_alert_notify_hook_ships_transitions_with_trace_ids(tmp_path):
    from paddle_tpu.observability import alerts

    r = MetricRegistry()
    h = r.histogram("nt_lat_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05, exemplar="trace-fast")
    h.observe(5.0, exemplar="trace-slow")
    shipped = []
    log = tmp_path / "ship.jsonl"
    rule = alerts.Rule("lat_high", metric="nt_lat_seconds_count", op=">",
                       threshold=1.0, for_s=0.0)
    eng = alerts.AlertEngine(rules=[rule], clock=lambda: 0.0,
                             notify=shipped.append)
    samples = obs_scrape.SampleSet().add_families(r.snapshot())
    eng.evaluate(samples, now=1.0)
    assert len(shipped) == 1 and shipped[0]["to"] == "firing"
    assert shipped[0]["trace_ids"] == ["trace-fast", "trace-slow"]
    # JSONL shipper sugar + flap safety (one transition per state change)
    eng2 = alerts.AlertEngine(rules=[alerts.Rule(
        "lat_high", metric="nt_lat_seconds_count", op=">", threshold=1.0,
        for_s=0.0, resolved_hold_s=1e9)], clock=lambda: 0.0,
        notify=str(log))
    eng2.evaluate(samples, now=1.0)
    eng2.evaluate(samples, now=2.0)  # still firing: no new transition
    empty = obs_scrape.SampleSet()
    eng2.evaluate(empty, now=3.0)    # resolved
    recs = [json.loads(l) for l in open(log)]
    assert [r_["to"] for r_ in recs] == ["firing", "resolved"]
    assert recs[0]["trace_ids"] == ["trace-fast", "trace-slow"]
    assert "time" in recs[0]


def test_alert_notify_failure_counted_not_raised():
    from paddle_tpu.observability import alerts

    r = MetricRegistry()
    r.gauge("nt_depth", "d").set(10.0)
    fails = obs.REGISTRY.get("alert_notify_failures_total")
    n0 = fails.value

    def bad_notify(rec):
        raise OSError("webhook down")

    eng = alerts.AlertEngine(
        rules=[alerts.Rule("deep", metric="nt_depth", op=">",
                           threshold=1.0, for_s=0.0)],
        clock=lambda: 0.0, notify=bad_notify)
    samples = obs_scrape.SampleSet().add_families(r.snapshot())
    out = eng.evaluate(samples, now=1.0)  # must not raise
    assert len(out) == 1
    assert fails.value == n0 + 1
    assert any(e.get("kind") == "alert_notify_failed"
               for e in obs_flight.events())


def test_burn_rate_transition_correlates_series_exemplars():
    """A burn-rate rule fires on slo_burn_rate_ratio{series=...}; its
    transition resolves trace ids through the series-prefixed histogram
    family (llm_ttft -> llm_ttft_seconds)."""
    from paddle_tpu.observability import alerts

    r = MetricRegistry()
    r.gauge("slo_burn_rate_ratio", "b", labelnames=("series",)).labels(
        series="llm_ttft").set(0.9)
    h = r.histogram("llm_ttft_seconds", "t", buckets=(0.1,))
    h.observe(4.2, exemplar="the-burner")
    eng = alerts.AlertEngine(rules=[alerts.Rule(
        "burn", kind="burn_rate", threshold=0.5, for_s=0.0)],
        clock=lambda: 0.0)
    out = eng.evaluate(obs_scrape.SampleSet().add_families(r.snapshot()),
                       now=1.0)
    fired = [t for t in out if t["to"] == "firing"]
    assert fired and fired[0]["trace_ids"] == ["the-burner"]


# ------------------------------------------------------------- trace_report
def test_trace_report_accepts_tracez_source(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "trace_report.py"))
    trp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trp)

    tr = _tracer()
    t = tr.start_trace("llm_request")
    with t.span("admission"):
        with t.span("llm_prefill_chunk"):
            time.sleep(0.002)
    t.add_span("decode", duration_s=0.05, ticks=10, tokens=10)
    t.end("ok")
    single = tmp_path / "one.json"
    single.write_text(json.dumps(t.to_dict()))
    tl = trp.load_timeline(tracez_path=str(single))
    assert set(tl) == {"admission", "llm_prefill_chunk", "decode"}
    assert tl["decode"]["total_us"] == pytest.approx(50000.0)
    assert tl["admission"]["total_us"] >= tl["llm_prefill_chunk"]["total_us"]
    # the store-dump shape works too, and joins with a census
    dump = tmp_path / "dump.json"
    tr.store.dump_json(str(dump))
    tl2 = trp.load_timeline(tracez_path=str(dump))
    assert set(tl2) == set(tl)
    rows = trp.join(tl2, {"decode": {"opcode": "", "flops": 1e6,
                                     "bytes": 0.0}})
    assert rows[0]["name"] == "decode" and rows[0]["matched"]


# ----------------------------------------------------------- store plumbing
def test_trace_store_dump_sibling_on_flight_dump(tmp_path):
    rec = obs_flight.FlightRecorder(capacity=16)
    rec.record("x")
    t = tracing.TRACER.start_trace("op")
    t.end("error")
    # an INJECTED tracer's store contributes to the same sibling dump —
    # per-engine isolation must not lose crash forensics
    private = _tracer()
    tp = private.start_trace("private_op")
    tp.end("shed")
    path = rec.dump(str(tmp_path), reason="manual test")
    assert os.path.exists(path)
    sib = [n for n in os.listdir(tmp_path) if n.startswith("traces_")]
    assert len(sib) == 1 and sib[0].startswith("traces_manual_test_")
    doc = json.load(open(tmp_path / sib[0]))
    ids = {x["trace_id"] for x in doc["traces"]}
    assert t.trace_id in ids and tp.trace_id in ids


# ------------------------------------------- inside a tick (ISSUE 25)
from paddle_tpu.observability.spans import PhaseClock  # noqa: E402


class _CheckedClock(PhaseClock):
    """The test's own debug flag: every closed phase is logged with its
    interval, and a phase opened while another runs is an error."""

    __slots__ = ("log",)

    def __init__(self, prefix, phases):
        super().__init__(prefix, phases)
        self.log = []

    def switch(self, phase):
        t = super().switch(phase)
        assert not self._on or self._phase == phase
        return t

    def _close(self, now):
        assert self._phase is not None and now >= self._t0
        self.log.append((self._phase, self._t0, now))
        super()._close(now)


def _tick_hist():
    h = obs.REGISTRY.get("llm_decode_tick_duration_seconds")._solo()
    return h.sum, h.count


def _paged_engine(model, tracer=None, slots=2, checked=False, **kw):
    from paddle_tpu.inference import llm_server

    kw.setdefault("page_size", 32)
    kw.setdefault("prefill_chunk", 32)
    eng = LLMEngine(model, max_batch_slots=slots, max_seq_len=128,
                    kv_layout="paged", tracer=tracer or _tracer(), **kw)
    if checked:
        eng._phases = _CheckedClock("llm_tick", llm_server._TICK_PHASES)
    return eng


def _prompts(seed, *lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 1024, n).astype(np.int32) for n in lengths]


def test_phase_clock_switches_on_one_read_and_stays_still_when_off():
    pc = _CheckedClock("t", ("a", "b"))
    assert pc.switch("a") == 0.0 and pc.end() == 0.0  # begin() never ran
    assert pc.count == {"a": 0, "b": 0}
    pc.begin()
    t0 = pc.switch("a")
    t1 = pc.switch("b")
    t2 = pc.switch("a")
    t3 = pc.end()
    assert t0 <= t1 <= t2 <= t3 and pc.end() == 0.0
    assert [(p, a, b) for p, a, b in pc.log] == [
        ("a", t0, t1), ("b", t1, t2), ("a", t2, t3)]  # shared boundaries
    assert pc.count == {"a": 2, "b": 1}
    assert sum(pc.seconds.values()) == pytest.approx(t3 - t0, abs=1e-9)
    with pytest.raises(KeyError):
        pc.begin()
        pc.switch("not_a_phase")
    pc.end()
    obs.disable()
    try:
        before = dict(pc.seconds), dict(pc.count)
        pc.begin()
        assert pc.switch("a") == 0.0 and pc.end() == 0.0
        assert (pc.seconds, pc.count) == before
    finally:
        obs.enable()


def test_tick_phases_exhaust_the_tick_and_feed_the_goodput_carves(model):
    """50+ ticks with admissions, a three-chunk prompt and finishing
    requests: the phases add up to the tick histogram, every count is
    right, no two phases overlap, and the goodput carves are sums of
    phases, boundary for boundary."""
    eng = _paged_engine(model, checked=True)
    s0, c0 = _tick_hist()
    chunks0 = obs.REGISTRY.get("llm_prefill_chunks_total").value
    lens, news = (70, 10, 20, 40, 33, 5), (5, 30, 3, 9, 14, 25)
    futs = [eng.submit(p, max_new_tokens=k)
            for p, k in zip(_prompts(1, *lens), news)]
    eng.run_until_complete()
    assert [len(f.result(timeout=1)) for f in futs] == list(news)
    s1, c1 = _tick_hist()
    ph = eng.stats()["tick_phases"]
    ticks = c1 - c0
    assert ticks >= 50
    total = sum(ph["seconds"].values())
    assert total == pytest.approx(s1 - s0, rel=0.02)
    assert total <= s1 - s0  # the phases lie INSIDE the tick's span
    cnt = ph["count"]
    assert cnt["expire"] == cnt["admit"] == ticks
    assert cnt["first_token_sync"] == len(futs)  # one an admission
    n_chunks = obs.REGISTRY.get("llm_prefill_chunks_total").value - chunks0
    assert cnt["prefill_dispatch"] == cnt["prefill_stage"] == n_chunks == 10
    # one token a decode tick and a slot.  A tick stages one program — the
    # chunk rides it (llm_mixed, under prefill_dispatch) beside whatever
    # rows decode — and every program dispatched is read once
    assert cnt["decode_stage"] == cnt["decode_sync"] \
        == cnt["decode_dispatch"] + cnt["prefill_dispatch"]
    assert 0 < cnt["decode_dispatch"] and cnt["decode_sync"] <= ticks
    assert eng.stats()["tick_pipeline"]["mixed"] > 0
    assert all(cnt[p] == 0 for p in cnt if p.startswith("spec_"))
    assert ph["host_s"] == pytest.approx(
        total - ph["seconds"]["decode_sync"]
        - ph["seconds"]["first_token_sync"], abs=1e-9)
    # exclusive and exhaustive: within a tick each phase starts on the
    # clock read that closed the one before it
    log = eng._phases.log
    assert len(log) == sum(cnt.values())
    gaps = [b[1] - a[2] for a, b in zip(log, log[1:])]
    assert all(g >= 0 for g in gaps)  # never overlap
    assert sum(g == 0 for g in gaps) == len(log) - ticks  # chained in-tick
    # one source of timing: the ledger's buckets ARE the phases
    sec = ph["seconds"]
    buckets = eng._goodput.check()["buckets"]
    assert buckets["decode"] == pytest.approx(
        sec["decode_stage"] + sec["decode_dispatch"] + sec["decode_sync"],
        abs=2e-6)
    assert buckets["prefill"] == pytest.approx(sec["prefill_dispatch"],
                                               abs=2e-6)
    # /metrics carries the same seconds
    fam = obs.REGISTRY.get("llm_tick_phase_seconds_total")
    pub = {lv[0]: ch.value for lv, ch in fam.series()}
    assert set(pub) == set(sec)


def test_tick_phase_counter_family_follows_the_engines_accumulators(model):
    fam = obs.REGISTRY.get("llm_tick_phase_seconds_total")
    before = {lv[0]: ch.value for lv, ch in fam.series()}
    eng = _paged_engine(model)
    eng.submit(_prompts(2, 12)[0], max_new_tokens=4)
    eng.run_until_complete()
    sec = eng.stats()["tick_phases"]["seconds"]
    after = {lv[0]: ch.value for lv, ch in fam.series()}
    for p, v in sec.items():
        assert after[p] - before[p] == pytest.approx(v, abs=1e-9)


def test_tick_phases_of_a_speculative_and_of_a_default_engine(model):
    eng = _paged_engine(model, spec_k=3, checked=True)
    s0, _ = _tick_hist()
    f = eng.submit(_prompts(3, 20)[0], max_new_tokens=12)
    eng.run_until_complete()
    assert len(f.result(timeout=1)) == 12
    s1, _ = _tick_hist()
    ph = eng.stats()["tick_phases"]
    cnt, sec = ph["count"], ph["seconds"]
    assert cnt["spec_dispatch"] == cnt["spec_sync"] == cnt["spec_accept"] \
        == cnt["spec_draft"] == eng.stats()["spec"]["verify_calls"] > 0
    assert sum(sec.values()) == pytest.approx(s1 - s0, rel=0.02)
    b = eng._goodput.check()["buckets"]
    assert b["verify"] + b["spec_rollback_waste"] == pytest.approx(
        sec["spec_draft"] + sec["spec_dispatch"] + sec["spec_sync"],
        abs=2e-6)
    # every option at its default: one admission and one chunk a tick, so
    # the second request's first token comes a tick after the first's
    bare = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                     tracer=_tracer())
    futs = [bare.submit(p, max_new_tokens=4) for p in _prompts(4, 9, 17)]
    bare.run_until_complete()
    assert all(len(f.result(timeout=1)) == 4 for f in futs)
    cnt = bare.stats()["tick_phases"]["count"]
    assert cnt["first_token_sync"] == cnt["prefill_dispatch"] == 2
    # the two programs that carried a chunk, and four that decoded: a first
    # token is read with the program that carried its chunk, and the second
    # request's chunk went out before the first's token was read
    assert cnt["decode_sync"] == 6 and cnt["decode_dispatch"] == 4
    assert not any(cnt[p] for p in cnt if p.startswith("spec_"))
    bare._goodput.check()


def test_tick_phases_do_not_move_with_metrics_disabled(model):
    eng = _paged_engine(model)
    eng.submit(_prompts(5, 12)[0], max_new_tokens=3)
    eng.run_until_complete()
    before = eng.stats()["tick_phases"]
    obs.disable()
    try:
        f = eng.submit(_prompts(5, 40)[0], max_new_tokens=6)
        eng.run_until_complete()
        assert len(f.result(timeout=1)) == 6
    finally:
        obs.enable()
    assert eng.stats()["tick_phases"] == before
    eng._goodput.check()


def test_admission_blocked_names_what_held_the_queue_head(model):
    eng = _paged_engine(model, slots=1)
    long_, short = _prompts(6, 60, 8)  # 60 tokens = two 32-token chunks
    eng.submit(long_, max_new_tokens=3)
    eng.submit(short, max_new_tokens=2)
    blocked = lambda: eng.stats()["admission_blocked"]  # noqa: E731
    eng.step()  # tick 1 pops the long prompt: nothing waited on entry
    assert blocked() == {"prefill_busy": 0, "no_slot": 0, "no_pages": 0,
                         "no_adapter_page": 0}
    eng.step()  # tick 2: its second chunk holds the prefill lane
    assert blocked()["prefill_busy"] == 1 and blocked()["no_slot"] == 0
    eng.step()  # tick 3: the only slot is decoding
    assert blocked() == {"prefill_busy": 1, "no_slot": 1, "no_pages": 0,
                         "no_adapter_page": 0}
    # its first token is read a tick after the program that carried its
    # final chunk was dispatched, the slot joined the decode a tick after
    # that and is freed where its last token is READ, a tick after the
    # program that computed it was dispatched (one program stays in
    # flight): ticks 4, 5 and 6 still find it taken, tick 7 admits the
    # short one, then no more waits
    eng.run_until_complete()
    assert blocked() == {"prefill_busy": 1, "no_slot": 4, "no_pages": 0,
                         "no_adapter_page": 0}
    fam = obs.REGISTRY.get("llm_admission_blocked_ticks_total")
    assert {lv[0] for lv, _ in fam.series()} == set(blocked())
    # a pool too small for the queue head: no_pages, one reason a tick
    tight = _paged_engine(model, slots=2, num_pages=4, prefix_cache=False)
    a, b = _prompts(7, 60, 60)  # 2 pages each (+1 token), 3 allocatable
    fa = tight.submit(a, max_new_tokens=6)
    fb = tight.submit(b, max_new_tokens=2)
    tight.run_until_complete()
    assert len(fa.result(timeout=1)) == 6 and len(fb.result(timeout=1)) == 2
    held = tight.stats()["admission_blocked"]
    assert held["no_pages"] >= 3 and held["no_slot"] == 0  # a slot was free


def test_token_stamps_ttft_attribute_and_on_token(model):
    tracer = _tracer()
    eng = _paged_engine(model, tracer=tracer)
    ttft = obs.REGISTRY.get("llm_ttft_seconds")._solo()
    sum0 = ttft.sum
    seen = []

    def boom(i, tok, t):
        seen.append((i, tok, t))
        raise RuntimeError("a stream callback must not kill the pump")

    f = eng.submit(_prompts(8, 40)[0], max_new_tokens=9, on_token=boom,
                   trace_id="stamps")
    eng.run_until_complete()
    toks = f.result(timeout=1)
    assert [i for i, _, _ in seen] == list(range(9))
    assert [tok for _, tok, _ in seen] == toks
    ts = [t for _, _, t in seen]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    tr = tracer.store.get_trace("stamps")
    a = tr.root.attrs
    adm = tr.find_spans("admission")[0]
    assert a["ttft_s"] == pytest.approx(adm.start_s + adm.duration_s,
                                        abs=1e-3)
    assert a["ttft_s"] == pytest.approx(ttft.sum - sum0, abs=1e-3)
    gaps = [b - a for a, b in zip(ts, ts[1:])]
    assert a["token_gap_max_s"] == pytest.approx(max(gaps), abs=1e-9)
    assert a["token_gap_mean_s"] == pytest.approx(sum(gaps) / 8, abs=1e-9)
    # the decode summary is derived from the same stamps
    dec = tr.find_spans("decode")[0]
    assert dec.attrs == {"ticks": 8, "tokens": 8}


def test_preempted_request_keeps_its_earlier_token_stamps(model):
    """test_preempted_request_one_trace_both_episodes' engine: the pool
    runs dry, one request requeues and re-prefills; its stamps and its
    on_token indices run on through the requeue."""
    tracer = _tracer()
    eng = _paged_engine(model, tracer=tracer, num_pages=3,
                        prefix_cache=False)
    seen = {"a": [], "b": []}
    pa, pb = _prompts(25, 30, 30)
    fa = eng.submit(pa, max_new_tokens=4, trace_id="a",
                    on_token=lambda i, tok, t: seen["a"].append((i, tok, t)))
    fb = eng.submit(pb, max_new_tokens=4, trace_id="b",
                    on_token=lambda i, tok, t: seen["b"].append((i, tok, t)))
    eng.run_until_complete()
    victim = next(k for k in "ab" if tracer.store.get_trace(k)
                  .root.attrs.get("preempt_requeues"))
    tr = tracer.store.get_trace(victim)
    assert len(tr.find_spans("admission")) == 2
    for k, f in (("a", fa), ("b", fb)):
        assert [i for i, _, _ in seen[k]] == [0, 1, 2, 3]
        assert [tok for _, tok, _ in seen[k]] == f.result(timeout=1)
    ts = [t for _, _, t in seen[victim]]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    first_adm = tr.find_spans("admission")[0]
    # ttft_s is still the FIRST episode's first token, not the requeue's
    assert tr.root.attrs["ttft_s"] == pytest.approx(
        first_adm.start_s + first_adm.duration_s, abs=1e-3)
    assert tr.root.attrs["token_gap_max_s"] >= \
        tr.find_spans("admission")[1].duration_s  # the gap spans the requeue


def test_programs_and_scopes_are_named_and_change_no_token(model,
                                                            monkeypatch):
    """Names are metadata: each compiled program carries its own module
    name and the model's scopes, and greedy output is what an engine
    built with the scopes switched off emits."""
    import contextlib

    import jax

    eng = _paged_engine(model, spec_k=2)
    eng.warmup()
    B, M, C, K = eng.n_slots, eng.M, eng.prefill_chunk, eng.spec_k
    i32 = np.int32
    knobs = (np.zeros(B, bool), np.ones(B, np.float32), np.zeros(B, i32),
             np.ones(B, np.float32))
    key = jax.random.PRNGKey(0)
    head = (eng._params, eng._buffers, eng.caches)
    lora = eng._lora_args([0] * B)
    programs = {
        "llm_decode": (eng._decode_jit[1], head + (
            np.zeros((B, M), i32), np.zeros((B, 1), i32), eng._feed,
            np.ones(B, bool), np.zeros(B, i32), *knobs, eng._mask_all_true, key, np.uint32(0), *lora)),
        "llm_prefill_chunk": (eng._get_chunk_prefill(), head + (
            np.zeros((1, M), i32), np.zeros((1, C), i32), np.zeros(1, i32),
            i32(0), *eng._lora_args([0]))),
        "llm_spec_verify": (eng._get_verify(), head + (
            np.zeros((B, M), i32), np.zeros((B, 1), i32),
            np.zeros((B, K), i32), np.zeros(B, i32), *knobs, key,
            np.uint32(0), *lora)),
    }
    for name, (jit, args) in programs.items():
        hlo = jit.lower(*args).compile().as_text()
        assert hlo.startswith(f"HloModule jit_{name}"), hlo[:80]
        for scope in ("attention", "mlp", "final_norm", "lm_head"):
            assert f"jit({name})/" in hlo and f"/{scope}/" in hlo, \
                (name, scope)
    assert "/sampler/" in programs["llm_decode"][0].lower(
        *programs["llm_decode"][1]).compile().as_text()

    def greedy():
        e = _paged_engine(model)
        futs = [e.submit(p, max_new_tokens=k) for p, k in
                zip(_prompts(1, 70, 10, 20, 40), (5, 12, 3, 9))]
        e.run_until_complete()
        return [f.result(timeout=1) for f in futs]

    named = greedy()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert greedy() == named


def test_profiler_trace_holds_phases_nested_in_the_tick_on_one_line(
        model, tmp_path):
    """A short jax.profiler trace of the tiny engine: llm_tick.decode_sync
    events lie inside llm_decode_tick events of the SAME host line, so the
    phases are on the clock the device's gaps are read on."""
    import glob

    import jax
    from jax.profiler import ProfileData

    eng = _paged_engine(model)
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.submit(_prompts(9, 40)[0], max_new_tokens=6)
        eng.run_until_complete()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    found = 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events]
            ticks = [(a, b) for n, a, b in ev if n == "llm_decode_tick"]
            syncs = [(a, b) for n, a, b in ev
                     if n == "llm_tick.decode_sync"]
            if not syncs:
                continue
            assert ticks, "phases on a line that has no tick"
            for a, b in syncs:
                assert any(ta <= a and b <= tb for ta, tb in ticks)
            names = {n for n, _, _ in ev if n.startswith("llm_tick.")}
            assert {"llm_tick.admit", "llm_tick.prefill_dispatch",
                    "llm_tick.first_token_sync", "llm_tick.decode_stage",
                    "llm_tick.bookkeep"} <= names
            found += len(syncs)
    # 6 tokens: the two programs that carried the prompt's chunks (the
    # second brings the first token), then five decode programs
    assert found == 7
