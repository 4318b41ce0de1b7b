"""Profiling plane (ISSUE 14): always-on compile & device-memory
telemetry, plus :class:`ProfilingSession` — a ``jax.profiler.trace()``
window whose per-HLO XPlane summary is filed under the owning PR-8 span
(one instrumentation point, three sinks: span tree, flight recorder,
metrics).

Compile telemetry
-----------------
Two silent killers of a compiled fleet are watched here:

- ``jit_compiles_total{fn}`` counts every compiled-program construction
  the engine / train step report through :func:`record_compile` (labeled
  by program family: prefill, decode, verify, ...), plus every XLA
  backend compile ``jax.monitoring`` observes (``fn="backend"`` — the
  catch-all that sees dtype/shape re-traces that never miss a Python
  jit cache).
- ``jit_recompiles_total{fn}`` counts only compiles AFTER
  :func:`mark_warm` (the engine calls it at the end of ``warmup()``).
  A warm process should never compile; the ``recompile_storm`` default
  alert rule is a delta over this family.

``install_compile_hooks()`` is idempotent and lazy: ``jax.monitoring``
is imported on first use, so this module stays importable in a
stdlib-only context (same contract as ``metrics``/``scrape``).

Device-memory telemetry
-----------------------
:func:`poll_device_memory` reads ``device.memory_stats()`` per device
into ``hbm_in_use_bytes`` / ``hbm_limit_bytes`` /
``hbm_utilization_ratio`` gauges and returns the JSON shape served on
``stats()["device_memory"]`` and ``/varz``.  CPU backends return no
memory stats — the poll yields ``[]`` there, gauges untouched, so every
consumer (fleetwatch, /varz) renders a dash instead of a lie.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import threading
import time

from . import metrics as _metrics
from . import flight_recorder as _flight
from . import goodput as _goodput
from . import xplane as _xplane

__all__ = [
    "install_compile_hooks", "record_compile", "mark_warm", "is_warm",
    "census_compiles", "poll_device_memory", "ProfilingSession",
    "BACKEND_COMPILE_EVENT",
]

#: The jax.monitoring duration event one XLA backend compile emits.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_M_COMPILES = _metrics.counter(
    "jit_compiles_total",
    "Compiled-program constructions by family (engine jit-cache misses, "
    "the train step's first trace) plus XLA backend compiles observed "
    "via jax.monitoring (fn=\"backend\")",
    labelnames=("fn",))
_M_RECOMPILES = _metrics.counter(
    "jit_recompiles_total",
    "Compiles AFTER mark_warm() (warmup() completed) — a warm process "
    "should never compile, so any delta here is a recompilation storm",
    labelnames=("fn",))
_M_COMPILE_S = _metrics.histogram(
    "jit_compile_seconds",
    "XLA backend compile durations (jax.monitoring "
    "backend_compile_duration events)",
    buckets=(0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0, 300.0))
_M_LAST_COMPILE = _metrics.gauge(
    "jit_last_compile_unix_seconds",
    "Wall-clock stamp of the most recent observed compile — fleetwatch "
    "renders it as a last-compile age column")
_M_HBM_USED = _metrics.gauge(
    "hbm_in_use_bytes", "Device memory currently allocated, per device",
    labelnames=("device",))
_M_HBM_LIMIT = _metrics.gauge(
    "hbm_limit_bytes", "Device memory capacity, per device",
    labelnames=("device",))
_M_HBM_RATIO = _metrics.gauge(
    "hbm_utilization_ratio", "in_use / limit per device (0 when the "
    "backend reports no limit)",
    labelnames=("device",))
_M_PROF_SESSIONS = _metrics.counter(
    "profile_sessions_total", "ProfilingSession windows completed")
_M_PROF_EXTRACT_S = _metrics.gauge(
    "profile_extract_seconds",
    "Wall seconds spent parsing + aggregating the last session's XPlane "
    "dump")
_M_PROF_OPS = _metrics.gauge(
    "profile_ops_count",
    "Distinct HLO ops extracted from the last session's dump")

_state = {"installed": False, "warm": False}
_lock = threading.Lock()
_census_thread = threading.local()


# ------------------------------------------------------- compile telemetry
def record_compile(fn, seconds=None, warm=None):
    """One compiled-program construction of family ``fn`` (an engine
    jit-cache miss, the train step's first trace).  ``warm=None`` reads
    the process warm flag; a warm compile also counts as a recompile."""
    fn = str(fn)
    _M_COMPILES.labels(fn=fn).inc()
    _M_LAST_COMPILE.set(time.time())  # tpulint: disable=impure-trace
    if seconds is not None:
        _M_COMPILE_S.observe(float(seconds))
        # goodput ledger (ISSUE 20): backend-compile seconds are the one
        # timed compile source, carved out of the active train ledger's
        # surrounding `step` section into its `compile` bucket
        _goodput.on_compile(float(seconds))
    if _state["warm"] if warm is None else warm:
        _M_RECOMPILES.labels(fn=fn).inc()


def _on_backend_compile(duration_s):
    if getattr(_census_thread, "on", False):
        # a census's ahead-of-time compile: counted under its own label,
        # never a recompile (the listener runs on the compiling thread)
        record_compile("census", seconds=duration_s, warm=False)
    else:
        record_compile("backend", seconds=duration_s)


@contextlib.contextmanager
def census_compiles():
    """The compiles this thread makes inside are a census's (``Compiled``
    objects built to be read, ``LLMEngine.program_census()``): they land
    on ``jit_compiles_total{fn="census"}`` and never on
    ``jit_recompiles_total``, so an operator's census of a warm engine
    does not trip ``recompile_storm``."""
    _census_thread.on = True
    try:
        yield
    finally:
        _census_thread.on = False


def install_compile_hooks():
    """Register the ``jax.monitoring`` backend-compile listener once
    (idempotent; safe to call from every engine/train-step __init__).
    Returns True when the listener is active."""
    with _lock:
        if _state["installed"]:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False

        def listener(event, duration_secs, **_kw):
            if event == BACKEND_COMPILE_EVENT:
                _on_backend_compile(duration_secs)

        monitoring.register_event_duration_secs_listener(listener)
        _state["installed"] = True
        return True


def mark_warm(warm=True):
    """Declare the process warm: every expected program is compiled
    (``LLMEngine.warmup()`` calls this on success).  Compiles observed
    after this point land on ``jit_recompiles_total`` and trip the
    ``recompile_storm`` default alert rule."""
    _state["warm"] = bool(warm)


def is_warm():
    return _state["warm"]


# ------------------------------------------------- device-memory telemetry
def poll_device_memory(devices=None):
    """Read ``memory_stats()`` off every device into the hbm_* gauges;
    return the ``stats()["device_memory"]`` / ``/varz`` JSON shape
    (one dict per device that actually reports; ``[]`` on CPU)."""
    if devices is None:
        try:
            import jax
            devices = jax.devices()
        except Exception:
            return []
    rows = []
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
        in_use = int(ms.get("bytes_in_use", 0))
        limit = int(ms.get("bytes_limit")
                    or ms.get("bytes_reservable_limit") or 0)
        ratio = in_use / limit if limit else 0.0
        _M_HBM_USED.labels(device=label).set(in_use)
        _M_HBM_LIMIT.labels(device=label).set(limit)
        _M_HBM_RATIO.labels(device=label).set(ratio)
        rows.append({"device": label, "bytes_in_use": in_use,
                     "bytes_limit": limit,
                     "utilization": round(ratio, 6)})
    return rows


# --------------------------------------------------------- ProfilingSession
class ProfilingSession:
    """``jax.profiler.trace()`` around a window of work (Python frames
    off: they cost ~100k events a second), reduced on exit by the plane's
    one reducer (``xplane.device_seconds``) and filed three ways: as child
    spans of an ``xplane_profile`` span on the owning PR-8 trace, as a
    flight recorder event, and on the ``profile_*`` gauges.

    ::

        trace = obs.start_trace("train_window")
        with ProfilingSession(trace=trace, census=census) as prof:
            for _ in range(n):
                step(batch)
        prof.by_scope         # device seconds by (program, named scope)
        table = prof.summary  # "module/instruction" -> {count, total_us, ...}
        path  = prof.dump_path        # feed tools/trace_report.py --xplane

    ``census`` is ``{module: {instruction: row}}`` (``census.by_module``,
    ``LLMEngine.program_census()``); without one ``by_scope`` still tells
    the programs apart and every event is unmatched.  ``logdir=None`` uses
    a fresh temp dir (kept — the dump is the artifact ``trace_report
    --xplane`` consumes).  A backend that cannot profile (no profiler
    plugin, a profiler already running) degrades to an empty summary with
    the failure recorded on the span, never an exception out of
    ``__exit__``: a profiling window must not kill the workload it
    observes."""

    def __init__(self, logdir=None, trace=None, top_k=12, census=None):
        from . import tracing as _tracing  # local: avoid import cycle
        self.logdir = logdir or tempfile.mkdtemp(prefix="paddle_xprof_")
        self.top_k = int(top_k)
        self.trace = trace if trace is not None else _tracing.NULL_TRACE
        self.census = census
        self.summary = None
        self.by_scope = None
        self.dump_path = None
        self.error = None
        # what the session itself costs: seconds to start the profiler, to
        # stop it (the dump is written there) and to read the dump
        self.start_s = self.stop_s = self.extract_s = None
        self._span = None
        self._t0 = None

    def __enter__(self):
        install_compile_hooks()
        import jax
        self._span = self.trace.span("xplane_profile",
                                     logdir=self.logdir).open()
        self._t0 = time.perf_counter()
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
        except Exception as e:  # profiler already active / unsupported
            self.error = repr(e)
            self._span.set_attr("error", self.error)
        self.start_s = time.perf_counter() - self._t0
        return self

    def __exit__(self, etype, exc, tb):
        import jax
        window_s = time.perf_counter() - self._t0
        if self.error is None:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                self.error = repr(e)
        t_extract = time.perf_counter()
        self.stop_s = t_extract - self._t0 - window_s
        self.summary = {}
        if self.error is None:
            try:
                self.dump_path = _xplane.find_dump(self.logdir)
                self.by_scope = _xplane.device_seconds(
                    _xplane.load_xspace(self.dump_path), self.census)
                self.summary = _xplane.timeline(self.by_scope, self.census)
            except Exception as e:
                self.error = repr(e)
        self.extract_s = time.perf_counter() - t_extract
        # the summary is in order of device time
        for name, row in list(self.summary.items())[:self.top_k]:
            self.trace.add_span(
                f"hlo:{name}", duration_s=row["total_us"] / 1e6,
                count=row["count"], hlo_module=row["hlo_module"],
                scope=row["scope"])
        self._span.set_attr("ops_extracted", len(self.summary))
        self._span.set_attr("device_us", round(sum(
            r["total_us"] for r in self.summary.values()), 3))
        if self.dump_path:
            self._span.set_attr("dump", self.dump_path)
        if self.error is not None:
            self._span.set_attr("error", self.error)
        self._span.close()
        _M_PROF_SESSIONS.inc()
        _M_PROF_EXTRACT_S.set(self.extract_s)
        _M_PROF_OPS.set(len(self.summary))
        _flight.record_event(
            "xplane_profile", window_s=round(window_s, 6),
            ops=len(self.summary), dump=self.dump_path,
            error=self.error)
        return False
