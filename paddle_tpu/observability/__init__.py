"""Unified observability layer: metrics, spans, and the telemetry plane.

The numbers half of the paper stack's host-tracer/device-tracer/cost-model
triple: a dependency-free process-global metrics registry
(`observability.metrics`) and span events that feed both the registry and
the native chrome-trace buffer (`observability.spans` via
`profiler.RecordEvent`).  Every built-in hot path — sharded train step,
checkpoint commit protocol, TCPStore client, recovery supervisor, LLM
server — registers its series here at import time, so
``paddle_tpu.observability.render_prometheus()`` is a complete `/metrics`
payload the moment the process starts, and ``tools/metrics_lint.py`` can
police the namespace without running a workload.

On top of the registry sits the telemetry plane (ISSUE 5):

- `observability.exporter` — stdlib HTTP endpoints: `/metrics`
  (Prometheus text), `/healthz` (component healthchecks), `/varz` (JSON
  snapshot); opt-in via ``LLMEngine(metrics_port=...)``,
  ``run_with_recovery(telemetry_port=...)`` or the launcher's
  ``--metrics_port``;
- `observability.flight_recorder` — a bounded black-box event ring dumped
  to JSONL (+ chrome trace) on crashes, preemptions and watchdog trips;
- `observability.slo` — deterministic sliding-window p50/p95/p99 and
  burn-rate tracking against configurable SLO targets.

And on top of the telemetry plane, the alerting plane (ISSUE 7) — the
first CONSUMER of the endpoints:

- `observability.scrape` — Prometheus text-format parser (the inverse of
  ``render_prometheus()``) plus a multi-target fleet scraper with
  per-target monotonic deadlines, bounded retry and staleness tracking;
- `observability.alerts` — declarative threshold / burn-rate / absence /
  delta rules with `for`-duration hysteresis, a deterministic
  inactive→pending→firing→resolved state machine, `/alertz` state on
  ``TelemetryServer``, and ``AlertPolicy`` actuation that drives
  ``run_with_recovery`` / ``ElasticManager`` restart decisions off the
  scraped series (``tools/fleetwatch.py`` is the operator CLI).

And orthogonal to the aggregate planes, the forensic plane (ISSUE 8):

- `observability.tracing` — request-scoped tracing: per-request span
  trees carried by an explicit context object, tail-sampled into a
  bounded store served on ``TelemetryServer`` `/tracez`, correlated to
  the aggregate planes via flight-recorder ``trace_id`` fields and
  OpenMetrics histogram EXEMPLARS (``# {trace_id="..."}`` annotations on
  `/metrics` that ``parse_prometheus`` round-trips).

And below the host boundary, the profiling plane (ISSUE 14):

- `observability.xplane` — dependency-free reader for the
  ``.xplane.pb`` dumps ``jax.profiler.trace()`` writes (hand-rolled
  protobuf wire parsing; no tensorflow/protobuf import), decoding
  per-HLO device events and reducing them by a census of the compiled
  programs to device seconds by (program, named scope)
  (``device_seconds``; ``tools/trace_report.py --xplane``);
- `observability.profiling` — ``ProfilingSession`` (a profiler window
  filed under the owning span), compile telemetry
  (``jit_compiles_total`` / ``jit_recompiles_total`` feeding the
  ``recompile_storm`` alert rule) and device-memory telemetry
  (``hbm_*`` gauges from ``device.memory_stats()``).

And joining the profiling plane against the cost model, the roofline
residual plane (ISSUE 17):

- `observability.roofline` — per-HLO measured-vs-predicted attribution
  (min-time roofline ``max(flops/peak_flops, bytes/peak_bw)`` vs XPlane
  per-op µs), compute-/memory-bound classification, content-addressed
  ``ROOFLINE_<round>.json`` rounds and the per-op regression sentinel
  (``tools/roofline_report.py --diff``); exports
  ``roofline_residual_ratio{op}`` / ``roofline_bound_fraction{bound}``
  and ``roofline_regressions_total`` (the ``roofline_regression``
  default delta alert rule's series).

Quick start::

    import paddle_tpu as paddle
    obs = paddle.observability
    srv = obs.start_exporter(port=9100)    # /metrics /healthz /varz
    ...train / serve...
    print(obs.render_prometheus())         # Prometheus text exposition
    print(obs.slo.summary())               # sliding-window percentiles
    obs.dump_jsonl("metrics.jsonl")        # append-only local time series
    obs.flight_recorder.dump("black_box")  # forensic event dump
    srv.stop()
    obs.disable()                          # per-call cost -> one dict lookup
"""
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricRegistry, REGISTRY,
    counter, gauge, histogram, enable, disable, enabled,
    snapshot, render_prometheus, dump_jsonl, log_buckets,
    DEFAULT_TIME_BUCKETS,
)
from .spans import span  # noqa: F401
from .flight_recorder import FlightRecorder, record_event  # noqa: F401
from .exporter import TelemetryServer, start_exporter  # noqa: F401
from .slo import SLOTracker, SLORegistry, SLOS  # noqa: F401
from .scrape import (  # noqa: F401
    parse_prometheus, SampleSet, Scraper, ScrapeTarget,
)
from .alerts import (  # noqa: F401
    Rule, AlertEngine, AlertPolicy, AlertDecision, default_rules,
    JsonlNotifier,
)
from .tracing import (  # noqa: F401
    Trace, Tracer, TraceStore, TRACES, TRACER, NULL_TRACE, start_trace,
)
from .xplane import (  # noqa: F401
    parse_xspace, load_xspace, find_dump, device_seconds, per_op_summary,
)
from .profiling import (  # noqa: F401
    ProfilingSession, install_compile_hooks, record_compile, mark_warm,
    poll_device_memory,
)
from .roofline import (  # noqa: F401
    predict_op, residual_rows, build_report, merge_reports, diff_reports,
    record_diff, export_gauges, save_round, load_round, newest_round,
)
from . import metrics  # noqa: F401
from . import spans  # noqa: F401
from . import flight_recorder  # noqa: F401
from . import exporter  # noqa: F401
from . import slo  # noqa: F401
from . import scrape  # noqa: F401
from . import alerts  # noqa: F401
from . import tracing  # noqa: F401
from . import xplane  # noqa: F401
from . import profiling  # noqa: F401
from . import roofline  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "REGISTRY",
    "counter", "gauge", "histogram", "enable", "disable", "enabled",
    "snapshot", "render_prometheus", "dump_jsonl", "log_buckets",
    "DEFAULT_TIME_BUCKETS", "span", "metrics", "spans",
    "FlightRecorder", "record_event", "flight_recorder",
    "TelemetryServer", "start_exporter", "exporter",
    "SLOTracker", "SLORegistry", "SLOS", "slo",
    "parse_prometheus", "SampleSet", "Scraper", "ScrapeTarget", "scrape",
    "Rule", "AlertEngine", "AlertPolicy", "AlertDecision", "default_rules",
    "JsonlNotifier", "alerts",
    "Trace", "Tracer", "TraceStore", "TRACES", "TRACER", "NULL_TRACE",
    "start_trace", "tracing",
    "parse_xspace", "load_xspace", "find_dump", "device_seconds",
    "per_op_summary",
    "xplane",
    "ProfilingSession", "install_compile_hooks", "record_compile",
    "mark_warm", "poll_device_memory", "profiling",
    "predict_op", "residual_rows", "build_report", "merge_reports",
    "diff_reports", "record_diff", "export_gauges", "save_round",
    "load_round", "newest_round", "roofline",
]
