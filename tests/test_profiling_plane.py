"""Profiling plane (ISSUE 14): compile & HBM telemetry, ProfilingSession
span filing, COW-fork donor links, and the fleet views that render them.

Oracles: a warmed paged engine decodes a full request with
``jit_compiles_total`` NOT moving (warmup covered every program), and a
forced dtype-flip afterwards moves BOTH compile counters and drives the
``recompile_storm`` default rule to firing under an injected clock;
``poll_device_memory`` publishes gauges from a fake device's
``memory_stats`` and returns ``[]`` on CPU (dash, not a lie);
``ProfilingSession`` files per-HLO ``hlo:*`` child spans under an
``xplane_profile`` span on the owning trace and survives a profiler that
cannot start; a second same-prefix request's admission span carries the
first request's trace id as ``prefix_donor`` and ``to_dict()`` renders
it under ``links``; the exporter's ``register_collect`` hook refreshes
gauges at scrape time (a raising collector is skipped, never a 500); and
fleetwatch/routerz render the new HBM / last-compile columns with dashes
for replicas that predate them.
"""
import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.router import Router
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import alerts as obs_alerts
from paddle_tpu.observability import profiling as prof
from paddle_tpu.observability import scrape as obs_scrape
from paddle_tpu.observability import tracing as obs_tracing
from paddle_tpu.observability.exporter import TelemetryServer
from paddle_tpu.observability.metrics import REGISTRY

pytestmark = pytest.mark.quick

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _counter_sum(name):
    fam = obs.snapshot().get(name)
    return sum(s["value"] for s in fam["series"]) if fam else 0.0


def _recompile_ss(value):
    s = obs_scrape.SampleSet()
    s.add("jit_recompiles_total", {"fn": "backend"}, value)
    return s


# ---------------------------------------------------------- compile counters
def test_record_compile_splits_cold_from_warm():
    prof.mark_warm(False)
    try:
        c0 = _counter_sum("jit_compiles_total")
        r0 = _counter_sum("jit_recompiles_total")
        prof.record_compile("probe")          # cold: not a recompile
        assert _counter_sum("jit_compiles_total") == c0 + 1
        assert _counter_sum("jit_recompiles_total") == r0
        prof.mark_warm()
        assert prof.is_warm()
        prof.record_compile("probe")          # warm: both move
        assert _counter_sum("jit_compiles_total") == c0 + 2
        assert _counter_sum("jit_recompiles_total") == r0 + 1
    finally:
        prof.mark_warm(False)


def test_warmup_quiet_then_dtype_flip_storms(model):
    """The acceptance sequence: warmup() compiles everything a request
    needs (counters then stay QUIET from the FIRST request on), and one forced
    dtype-flip re-trace afterwards moves both counters and fires the
    recompile_storm default rule."""
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=8)
    try:
        eng.warmup()
        assert prof.is_warm()
        rng = np.random.RandomState(3)

        # warmup covered EVERY program a request runs: the engine's jits
        # and the argument staging, which is host-side numpy — no eager
        # device op is left to compile under the first request
        quiet0 = _counter_sum("jit_compiles_total")
        for n_prompt, n_new in ((13, 4), (17, 3)):
            f = eng.submit(rng.randint(0, 1024, n_prompt).astype(np.int32),
                           max_new_tokens=n_new)
            eng.run_until_complete()
            assert len(f.result(timeout=1)) == n_new
        assert _counter_sum("jit_compiles_total") == quiet0

        # forced re-trace: same python callable, flipped dtype
        g = jax.jit(lambda x: x * 2 + 1)
        g(jnp.ones((4,), jnp.float32)).block_until_ready()
        c1 = _counter_sum("jit_compiles_total")
        r1 = _counter_sum("jit_recompiles_total")
        g(jnp.ones((4,), jnp.int32)).block_until_ready()
        assert _counter_sum("jit_compiles_total") > c1
        r2 = _counter_sum("jit_recompiles_total")
        assert r2 > r1

        # the default-rule alert engine sees the delta and fires
        eng2 = obs_alerts.AlertEngine(rules=obs_alerts.default_rules(),
                                      clock=lambda: 0.0)
        eng2.evaluate(_recompile_ss(r1), now=0.0)
        trs = eng2.evaluate(_recompile_ss(r2), now=10.0)
        storm = [t for t in trs if t["alert"] == "recompile_storm"]
        assert [t["to"] for t in storm] == ["firing"]
    finally:
        prof.mark_warm(False)
        eng.stop()


# --------------------------------------------------------- device memory
class _FakeDev:
    def __init__(self, platform="tpu", dev_id=0, stats=None, boom=False):
        self.platform = platform
        self.id = dev_id
        self._stats = stats
        self._boom = boom

    def memory_stats(self):
        if self._boom:
            raise RuntimeError("transport error")
        return self._stats


def test_poll_device_memory_publishes_gauges_from_fake_devices():
    rows = prof.poll_device_memory([
        _FakeDev(stats={"bytes_in_use": 768, "bytes_limit": 1024}),
        _FakeDev(dev_id=1, stats=None),          # no stats -> skipped
        _FakeDev(dev_id=2, boom=True),           # raising -> skipped
        _FakeDev(dev_id=3, stats={"bytes_in_use": 10,
                                  "bytes_reservable_limit": 100}),
    ])
    assert rows == [
        {"device": "tpu:0", "bytes_in_use": 768, "bytes_limit": 1024,
         "utilization": 0.75},
        {"device": "tpu:3", "bytes_in_use": 10, "bytes_limit": 100,
         "utilization": 0.1},
    ]
    g = REGISTRY.get("hbm_utilization_ratio")
    assert g.labels(device="tpu:0").value == 0.75
    assert REGISTRY.get("hbm_in_use_bytes").labels(device="tpu:3").value \
        == 10.0
    assert REGISTRY.get("hbm_limit_bytes").labels(device="tpu:0").value \
        == 1024.0


def test_poll_device_memory_empty_on_cpu(model):
    assert prof.poll_device_memory() == []  # CPU: no memory_stats
    eng = LLMEngine(model, max_batch_slots=1, max_seq_len=128)
    try:
        assert eng.stats()["device_memory"] == []
    finally:
        eng.stop()


# ------------------------------------------------------- ProfilingSession
def test_profiling_session_files_hlo_spans_under_owning_trace(tmp_path):
    tracer = obs_tracing.Tracer(store=obs_tracing.TraceStore(
        capacity=8, sample_every=1))
    trace = tracer.start_trace("train_window")
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 4), jnp.float32)
    jitted = jax.jit(lambda a, b: jnp.max(jnp.dot(a, b)))
    jitted(x, w).block_until_ready()
    sessions0 = _counter_sum("profile_sessions_total")
    with prof.ProfilingSession(logdir=str(tmp_path / "prof"),
                               trace=trace) as sess:
        for _ in range(2):
            jitted(x, w).block_until_ready()
    trace.end("ok")
    assert sess.error is None
    assert sess.summary and os.path.isfile(sess.dump_path)
    # the reduced rows are keyed "module/instruction"; this jax names the
    # instruction after the primitive (`dot_general.N`, once `dot.N`)
    dots = [r for k, r in sess.summary.items()
            if r["module"] and k == f"{r['module']}/{r['instruction']}"
            and r["instruction"].startswith("dot")]
    assert dots and all(r["module"].startswith("jit_") and r["count"] == 2
                        and r["total_us"] > 0 for r in dots)
    assert sess.by_scope["programs"][dots[0]["module"]]["calls"] == 2
    (span,) = trace.find_spans("xplane_profile")
    assert span.attrs["ops_extracted"] == len(sess.summary)
    assert span.attrs["device_us"] > 0
    hlo = [c for c in span.children if c.name.startswith("hlo:")]
    assert hlo and all(c.duration_s >= 0 for c in hlo)
    assert _counter_sum("profile_sessions_total") == sessions0 + 1
    assert REGISTRY.get("profile_ops_count").value == len(sess.summary)
    # the stored trace renders the whole thing on /tracez
    doc = tracer.store.get(trace.trace_id)
    assert doc is not None


def test_profiling_session_survives_unstartable_profiler(tmp_path):
    """A second session while one is live cannot start the profiler —
    the failure lands on the span/error field, never as an exception
    killing the profiled workload."""
    with prof.ProfilingSession(logdir=str(tmp_path / "outer")) as outer:
        with prof.ProfilingSession(logdir=str(tmp_path / "inner")) as inner:
            jnp.ones((2,)).block_until_ready()
        assert inner.error is not None
        assert inner.summary == {}
    assert outer.error is None  # inner's failure did not steal the trace


# ------------------------------------------------------- COW donor links
def test_cow_fork_links_admission_to_donor_trace(model):
    tracer = obs_tracing.Tracer(store=obs_tracing.TraceStore(
        capacity=64, sample_every=1))
    eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128,
                    kv_layout="paged", page_size=32, prefill_chunk=16,
                    prefix_cache=True, tracer=tracer)
    try:
        rng = np.random.RandomState(50)
        head = rng.randint(0, 1024, 40).astype(np.int32)
        p1 = np.concatenate([head, rng.randint(0, 1024, 4)
                             .astype(np.int32)])
        p2 = np.concatenate([head, rng.randint(0, 1024, 6)
                             .astype(np.int32)])
        f1 = eng.submit(p1, max_new_tokens=3, trace_id="donor-1")
        eng.run_until_complete()
        f2 = eng.submit(p2, max_new_tokens=3, trace_id="fork-2")
        eng.run_until_complete()
        f1.result(timeout=1), f2.result(timeout=1)
    finally:
        eng.stop()
    t2 = tracer.store.get_trace("fork-2")
    assert t2 is not None
    adm = t2.find_spans("admission")
    assert adm and adm[-1].attrs["prefix_donor"] == "donor-1"
    assert adm[-1].attrs["cached_tokens"] >= 32  # the shared full page
    links = t2.to_dict()["links"]
    assert {"span": "admission", "attr": "prefix_donor",
            "trace_id": "donor-1"} in links
    # the donor's own trace carries no self-link
    t1 = tracer.store.get_trace("donor-1")
    assert "links" not in t1.to_dict()


# ------------------------------------------------------ exporter collect
def test_exporter_register_collect_refreshes_at_scrape_time():
    g = obs.gauge("collect_probe_value", "test-only scrape-time probe")
    calls = {"n": 0}

    def collector():
        calls["n"] += 1
        g.set(float(calls["n"]))
        return {"polls": calls["n"]}

    def broken():
        raise RuntimeError("collector died")

    srv = TelemetryServer(port=0)
    srv.register_collect(broken)  # skipped, never a 500
    srv.register_collect(collector, varz_key="probe")
    srv.start()
    try:
        body = urllib.request.urlopen(srv.url + "/metrics",
                                      timeout=5).read().decode()
        assert calls["n"] == 1
        assert "collect_probe_value 1\n" in body
        varz = json.loads(urllib.request.urlopen(
            srv.url + "/varz", timeout=5).read().decode())
        assert calls["n"] == 2
        assert varz["probe"] == {"polls": 2}
        # the /varz metrics snapshot is taken AFTER the collectors ran
        assert varz["metrics"]["collect_probe_value"]["series"][0][
            "value"] == 2.0
    finally:
        srv.stop()


# ------------------------------------------------------------ fleet views
def test_fleetwatch_status_renders_hbm_and_compile_age():
    fw = _load_tool("fleetwatch")
    ss = obs_scrape.SampleSet()
    ss.add("hbm_utilization_ratio", {"target": "r1", "device": "tpu:0"},
           0.5)
    ss.add("jit_last_compile_unix_seconds", {"target": "r1"}, 1000.0)

    class _R:
        def __init__(self, name):
            self.target = type("T", (), {"name": name})
            self.ok = True
            self.duration_s = 0.001
            self.attempts = 1
            self.error = None

    out = fw.render_status([_R("r1"), _R("r2")], {"alerts": []},
                           now=0.0, samples=ss, wall_now=1042.0)
    row1 = [ln for ln in out.splitlines() if ln.startswith("r1")][0]
    row2 = [ln for ln in out.splitlines() if ln.startswith("r2")][0]
    assert "50%" in row1 and "42s" in row1
    assert "50%" not in row2  # no samples -> dashes
    assert " - " in row2 or row2.rstrip().endswith("-")


def test_fleetwatch_routerz_renders_dash_for_old_replicas():
    fw = _load_tool("fleetwatch")
    out = fw.render_routerz({"replicas": [
        {"name": "old", "state": "up", "target": "h:1", "restarts": 0},
        {"name": "new", "state": "up", "target": "h:2", "restarts": 1,
         "hbm_utilization_ratio": 0.731, "last_compile_age_s": 90.0},
    ], "affinity": {"entries": 0, "capacity": 1, "hits": 0, "misses": 0,
                    "hit_ratio": 0.0, "blocks": 1, "page_size": 32}})
    old = [ln for ln in out.splitlines() if ln.startswith("old")][0]
    new = [ln for ln in out.splitlines() if ln.startswith("new")][0]
    assert old.rstrip().endswith("-")
    assert "73%" in new and "90s" in new


def test_router_routerz_enriches_replicas_from_samples():
    r = Router([("r1", "127.0.0.1:1"), ("r2", "127.0.0.1:2")])
    try:
        ss = obs_scrape.SampleSet()
        ss.add("hbm_utilization_ratio", {"target": "r1",
                                         "device": "tpu:0"}, 0.25)
        ss.add("jit_last_compile_unix_seconds", {"target": "r1"},
               time.time() - 30.0)
        r._samples = ss
        doc = r.routerz()
        by_name = {d["name"]: d for d in doc["replicas"]}
        assert by_name["r1"]["hbm_utilization_ratio"] == 0.25
        assert 25.0 <= by_name["r1"]["last_compile_age_s"] <= 120.0
        # a replica with no samples keeps BOTH keys absent (old-doc shape)
        assert "hbm_utilization_ratio" not in by_name["r2"]
        assert "last_compile_age_s" not in by_name["r2"]
    finally:
        r.stop()


def test_router_routerz_kv_tiers_absent_not_zero():
    """Hierarchical-kv enrichment (PR 19): a replica exporting the tier
    families gets a kv_tiers block with per-tier hit attribution; a
    pre-tier replica keeps the key ABSENT (never an empty/zero block)."""
    r = Router([("r1", "127.0.0.1:1"), ("r2", "127.0.0.1:2")])
    try:
        ss = obs_scrape.SampleSet()
        ss.add("llm_kv_host_pool_bytes", {"target": "r1"}, 2.5e6)
        ss.add("llm_prefix_tier_hits_total",
               {"target": "r1", "tier": "hbm"}, 60.0)
        ss.add("llm_prefix_tier_hits_total",
               {"target": "r1", "tier": "host"}, 30.0)
        ss.add("llm_prefix_tier_hits_total",
               {"target": "r1", "tier": "disk"}, 10.0)
        r._samples = ss
        doc = r.routerz()
        by_name = {d["name"]: d for d in doc["replicas"]}
        tiers = by_name["r1"]["kv_tiers"]
        assert tiers["host_pool_bytes"] == 2500000
        assert tiers["hbm_hit_tokens"] == 60
        assert tiers["host_hit_tokens"] == 30
        assert tiers["disk_hit_tokens"] == 10
        assert tiers["lower_tier_hit_ratio"] == 0.4
        assert "kv_tiers" not in by_name["r2"]  # pre-PR-19 replica
    finally:
        r.stop()


def test_fleetwatch_routerz_renders_kv_tier_column():
    fw = _load_tool("fleetwatch")
    out = fw.render_routerz({"replicas": [
        {"name": "old", "state": "up", "target": "h:1", "restarts": 0},
        {"name": "new", "state": "up", "target": "h:2", "restarts": 0,
         "kv_tiers": {"host_pool_bytes": 2500000, "hbm_hit_tokens": 60,
                      "host_hit_tokens": 30, "disk_hit_tokens": 10,
                      "lower_tier_hit_ratio": 0.4}},
    ], "affinity": {"entries": 0, "capacity": 1, "hits": 0, "misses": 0,
                    "hit_ratio": 0.0, "blocks": 1, "page_size": 32}})
    assert "KVTIERS" in out.splitlines()[0]
    old = [ln for ln in out.splitlines() if ln.startswith("old")][0]
    new = [ln for ln in out.splitlines() if ln.startswith("new")][0]
    assert old.rstrip().endswith("-")  # absent tiers render a dash
    assert "2.5MB/40%" in new
