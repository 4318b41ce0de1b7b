"""Request-scoped tracing: per-request span trees with tail sampling.

The aggregate planes (metrics PR 2, telemetry PR 5, alerting PR 7) can say
*that* p99 TTFT is burning; nothing in the stack could say *which* request
blew the budget or *why* (a 7-chunk prefill?  a COW fork?  two
page-preempt-requeue episodes?  queue-wait behind a long prompt?).  This
module is that forensic layer — the per-request TTFT/e2e breakdowns the
Ragged Paged Attention and Gemma-on-TPU serving studies (PAPERS.md) treat
as the primary tuning signal:

- every traced operation gets a ``trace_id`` and a TREE of timed spans with
  structured attributes, carried by an EXPLICIT context object (the
  ``Trace``) — no thread-locals anywhere near jitted paths, the object
  rides on the request/supervisor that owns it;
- ONE instrumentation point lands in three sinks: the span tree here, the
  flight recorder (events gain a ``trace_id`` field), and the metrics
  registry via EXEMPLARS (``Histogram.observe(v, exemplar=trace_id)`` —
  ``render_prometheus()`` emits OpenMetrics-style ``# {trace_id="..."}``
  annotations that ``parse_prometheus()`` round-trips);
- completed traces land in a bounded in-memory :class:`TraceStore` under
  TAIL sampling: every error/shed/expired trace, every trace that was
  page-preempted/requeued, every SLO-violating trace (the `slo.py`
  targets mark violations at observe time), plus a deterministic 1-in-N
  of the healthy rest — the store can answer "show me a bad one" without
  retaining the fleet's entire traffic;
- the ``TelemetryServer`` serves the store on ``/tracez`` (list +
  fetch-by-id, JSON and chrome-trace per-trace export) and every
  flight-recorder black box gets a sibling ``traces_<reason>_*.json``
  dump, so a crash leaves the request timelines next to the event ring.

Disabled fast path (the PR-2 ``disable()`` contract): ``start_trace``
checks the same one module-level dict as every metric and returns the
:data:`NULL_TRACE` singleton — every span/attr/end call on it is a no-op
method, so instrumented hot paths stay benchmark-clean with observability
off (guarded by ``_bench_tracing`` in bench.py).

Timing discipline: span durations come from ``time.perf_counter()``
(monotonic); each trace carries ONE wall-clock stamp for joining with
external logs.

No jax / numpy imports (same contract as ``observability.metrics``).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import uuid
import weakref
from collections import OrderedDict

from . import metrics as _metrics
from . import flight_recorder as _flight

__all__ = [
    "Span", "Trace", "Tracer", "TraceStore", "TRACES", "TRACER",
    "NULL_TRACE", "start_trace", "stats",
]

_M_STARTED = _metrics.counter(
    "trace_started_total", "Request-scoped traces started")
_M_SAMPLED = _metrics.counter(
    "trace_sampled_total",
    "Completed traces retained by the tail sampler, by keep reason",
    labelnames=("reason",))
_M_DROPPED = _metrics.counter(
    "trace_dropped_total",
    "Completed healthy traces dropped by the tail sampler")
_M_STORE_DEPTH = _metrics.gauge(
    "trace_store_depth", "Traces currently retained in the in-memory store")
_M_EVICTED = _metrics.counter(
    "trace_store_evictions_total",
    "Stored traces evicted by the store's ring bound")


class Span:
    """One timed node of a trace tree.  ``start_s`` is relative to the
    trace start (perf_counter delta); attributes are plain JSON-safe
    values."""

    __slots__ = ("name", "start_s", "duration_s", "attrs", "error",
                 "children")

    def __init__(self, name, start_s, attrs=None):
        self.name = str(name)
        self.start_s = float(start_s)
        self.duration_s = None  # None while open
        self.attrs = dict(attrs) if attrs else {}
        self.error = None
        self.children: list[Span] = []

    def to_dict(self):
        d = {"name": self.name, "start_s": round(self.start_s, 6),
             "duration_s": round(self.duration_s, 6)
             if self.duration_s is not None else None}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.error is not None:
            d["error"] = self.error
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def span_count(self):
        return 1 + sum(c.span_count() for c in self.children)


class _SpanCtx:
    """Open-span handle: context manager (``with trace.span(...)``) or
    explicit ``open()``/``close()`` for spans held across engine ticks
    (a chunked-prefill admission stays open while decode ticks run)."""

    __slots__ = ("_trace", "_name", "_attrs", "span")

    def __init__(self, trace, name, attrs):
        self._trace = trace
        self._name = name
        self._attrs = attrs
        self.span = None

    def open(self):
        if self.span is None:
            self.span = self._trace._open(self._name, self._attrs)
        return self

    def close(self, error=None):
        if self.span is not None:
            self._trace._close(self.span, error=error)
            self.span = None
        return self

    def set_attr(self, key, value):
        if self.span is not None:
            self.span.attrs[str(key)] = value
        return self

    def __enter__(self):
        return self.open()

    def __exit__(self, etype, exc, tb):
        self.close(error=repr(exc) if exc is not None else None)
        return False


class Trace:
    """One traced operation: a ``trace_id`` plus a tree of spans rooted at
    the operation itself.

    The trace object IS the context: callers thread it explicitly (a
    ``_Request`` field, a supervisor local) — there is deliberately no
    ambient current-trace global, so jitted paths never consult
    thread-local state.  Span open/close through one trace must come from
    one logical thread at a time (the engine lock already serializes the
    request lifecycle); ``end()`` is idempotent and safe to race from a
    failing pump and a stopping caller.
    """

    __slots__ = ("trace_id", "name", "status", "start_unix", "duration_s",
                 "slo_violations", "sampled_reason", "root", "_t0",
                 "_stack", "_tracer", "_end_lock", "_ended")

    def __init__(self, tracer, name, attrs=None, trace_id=None):
        # an inherited id (router -> replica propagation) keeps both hops
        # of one request under a single /tracez document — the store
        # grafts same-id segments into one tree
        self.trace_id = str(trace_id) if trace_id else tracer._next_id()
        self.name = str(name)
        self.status = None  # set by end()
        # one wall stamp per trace: forensic joins with external logs share
        # NTP, not this process's boot clock (durations stay monotonic)
        self.start_unix = time.time()  # tpulint: disable=impure-trace
        self._t0 = time.perf_counter()
        self.duration_s = None
        self.slo_violations: list[str] = []
        self.sampled_reason = None  # stamped by TraceStore.offer
        self.root = Span(self.name, 0.0, attrs)
        self._stack = [self.root]
        self._tracer = tracer
        self._end_lock = threading.Lock()
        self._ended = False

    def __bool__(self):
        return True

    # ------------------------------------------------------------ spans
    def _now_s(self):
        return time.perf_counter() - self._t0

    def rel_s(self, t):
        """A ``time.perf_counter()`` stamp as seconds since the trace's
        start: the clock its spans' ``start_s`` are on."""
        return t - self._t0

    def _open(self, name, attrs):
        sp = Span(name, self._now_s(), attrs)
        parent = self._stack[-1] if self._stack else self.root
        parent.children.append(sp)
        self._stack.append(sp)
        return sp

    def _close(self, sp, error=None):
        sp.duration_s = max(0.0, self._now_s() - sp.start_s)
        if error is not None:
            sp.error = str(error)
        # defensive unwind: closing a span closes any child left open
        while self._stack and self._stack[-1] is not sp:
            if len(self._stack) == 1:
                return  # sp was already unwound (double close)
            dangling = self._stack.pop()
            if dangling.duration_s is None:
                dangling.duration_s = max(0.0,
                                          self._now_s() - dangling.start_s)
        if len(self._stack) > 1:
            self._stack.pop()

    def span(self, name, **attrs) -> _SpanCtx:
        """A child span of the innermost open span.  Use as a context
        manager, or hold the handle and ``open()``/``close()`` it across
        engine ticks."""
        return _SpanCtx(self, name, attrs)

    def add_span(self, name, duration_s, start_s=None, **attrs):
        """Attach a pre-measured span (e.g. a coalesced decode-tick
        summary) as a child of the innermost open span."""
        sp = Span(name,
                  self._now_s() - float(duration_s)
                  if start_s is None else float(start_s), attrs)
        sp.duration_s = max(0.0, float(duration_s))
        parent = self._stack[-1] if self._stack else self.root
        parent.children.append(sp)
        return sp

    # ------------------------------------------------------- attributes
    def set_attr(self, key, value):
        self.root.attrs[str(key)] = value

    def inc_attr(self, key, amount=1):
        self.root.attrs[key] = self.root.attrs.get(key, 0) + amount

    def mark_slo(self, series):
        """Record that an observation attributed to this trace violated
        the series' SLO target — the tail sampler keeps such traces."""
        s = str(series)
        if s not in self.slo_violations:
            self.slo_violations.append(s)

    def flight(self, kind, **fields):
        """A flight-recorder event correlated to this trace."""
        _flight.record_event(kind, trace_id=self.trace_id, **fields)

    # ------------------------------------------------------------ ending
    def end(self, status="ok", **attrs):
        """Finalize the trace (idempotent): close dangling spans, stamp
        the duration and hand the trace to the tracer's store for the
        tail-sampling decision."""
        with self._end_lock:
            if self._ended:
                return self
            self._ended = True
        dur = self._now_s()
        while len(self._stack) > 1:
            dangling = self._stack.pop()
            if dangling.duration_s is None:
                dangling.duration_s = max(0.0, dur - dangling.start_s)
        self.status = str(status)
        if attrs:
            self.root.attrs.update(attrs)
        self.duration_s = dur
        self.root.duration_s = dur
        self._tracer._finish(self)
        return self

    @property
    def ended(self):
        return self._ended

    # ---------------------------------------------------------- exports
    def links(self):
        """Cross-trace links: span attributes named ``*_donor`` hold
        another trace's trace_id (the COW-fork ``prefix_donor`` stamp on
        an admission span) — collected here so `/tracez` renders a COW
        storm as a navigable graph instead of a bare attribute."""
        out = []

        def walk(sp):
            for k, v in sp.attrs.items():
                if k.endswith("_donor") and v:
                    out.append({"span": sp.name, "attr": k,
                                "trace_id": str(v)})
            for c in sp.children:
                walk(c)
        walk(self.root)
        return out

    def to_dict(self):
        d = {
            "trace_id": self.trace_id,
            "name": self.name,
            "status": self.status,
            "start_unix": self.start_unix,
            "duration_s": round(self.duration_s, 6)
            if self.duration_s is not None else None,
            "slo_violations": list(self.slo_violations),
            "sampled_reason": self.sampled_reason,
            "attrs": dict(self.root.attrs),
            "spans": [c.to_dict() for c in self.root.children],
        }
        links = self.links()
        if links:
            d["links"] = links
        return d

    def span_tree(self):
        """Nested ``[name, [children...]]`` lists — the exact-tree
        assertion helper (attribute-free, deterministic)."""
        def walk(sp):
            return [sp.name, [walk(c) for c in sp.children]]
        return [walk(c) for c in self.root.children]

    def find_spans(self, name):
        """Depth-first list of spans named ``name`` anywhere in the tree."""
        out = []

        def walk(sp):
            if sp.name == name:
                out.append(sp)
            for c in sp.children:
                walk(c)
        for c in self.root.children:
            walk(c)
        return out

    def to_chrome_trace(self):
        """This trace as a chrome://tracing document (complete 'X' events;
        nesting is conveyed by time containment on one tid)."""
        events = []

        def walk(sp):
            events.append({
                "name": sp.name, "ph": "X", "pid": 0, "tid": 0,
                "ts": sp.start_s * 1e6,
                "dur": (sp.duration_s or 0.0) * 1e6,
                "args": dict(sp.attrs),
            })
            for c in sp.children:
                walk(c)
        walk(self.root)
        return {"traceEvents": events,
                "metadata": {"trace_id": self.trace_id,
                             "status": self.status}}


class _NullSpanCtx:
    __slots__ = ()

    def open(self):
        return self

    def close(self, error=None):
        return self

    def set_attr(self, key, value):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpanCtx()


class _NullTrace:
    """The disabled / sampled-off trace: every method is a no-op, and the
    object is falsy so call sites can skip optional work cheaply."""

    __slots__ = ()
    trace_id = ""
    name = ""
    status = None
    duration_s = None
    slo_violations = ()
    ended = True

    def __bool__(self):
        return False

    def span(self, name, **attrs):
        return _NULL_SPAN

    def add_span(self, name, duration_s, start_s=None, **attrs):
        return None

    def rel_s(self, t):
        return 0.0

    def set_attr(self, key, value):
        pass

    def inc_attr(self, key, amount=1):
        pass

    def mark_slo(self, series):
        pass

    def flight(self, kind, **fields):
        pass

    def end(self, status="ok", **attrs):
        return self

    def to_dict(self):
        return {}

    def span_tree(self):
        return []

    def find_spans(self, name):
        return []

    def links(self):
        return []


NULL_TRACE = _NullTrace()


class TraceStore:
    """Bounded in-memory store of completed traces under TAIL sampling.

    ``offer(trace)`` keeps:

    - every trace whose terminal status is not ``"ok"`` (errors, sheds,
      deadline expiries, engine stops) — reason ``"error"``;
    - every trace that was preempted/requeued mid-flight
      (``preempt_requeues`` root attribute) — reason ``"preempted"``;
    - every trace with a recorded SLO violation (``Trace.mark_slo``, fed
      by the existing `slo.py` targets) — reason ``"slo"``;
    - a deterministic 1-in-``sample_every`` of the healthy rest — reason
      ``"tail"`` (counter-based: same traffic, same decisions).

    Stored traces evict oldest-first past ``capacity`` — the store can
    never OOM a long-running server.
    """

    def __init__(self, capacity=256, sample_every=16):
        self.capacity = max(1, int(capacity))
        self.sample_every = max(0, int(sample_every))  # 0 = no tail keeps
        self._traces: "OrderedDict[str, Trace]" = OrderedDict()
        self._lock = threading.Lock()
        self._ok_seen = 0
        # local ints so stats() works with metrics disabled (the counters
        # above are the fleet-visible mirrors)
        self.sampled = 0
        self.dropped = 0
        self.evicted = 0
        # every live store contributes to the crash-dump sibling (an
        # engine with an injected tracer must not lose its forensics)
        self._created_seq = next(_STORE_SEQ)
        _ALL_STORES.add(self)

    def __len__(self):
        with self._lock:  # finish()/eviction mutate the store concurrently
            return len(self._traces)

    def keep_reason(self, trace):
        """The tail-sampling verdict for ``trace`` (None = drop).  Does
        not consume the 1-in-N counter."""
        if trace.status is not None and trace.status != "ok":
            return "error"
        if trace.root.attrs.get("preempt_requeues") \
                or trace.root.attrs.get("restart_episodes"):
            return "preempted"  # requeued requests / restarted runs
        if trace.slo_violations:
            return "slo"
        return None

    @staticmethod
    def _graft(primary, other):
        """Merge ``other`` (a same-id segment of the same request — e.g.
        the replica-side trace of a routed call) into ``primary``'s tree
        as one child span named after ``other``.  Offsets come from the
        segments' wall stamps (the only clock two processes share)."""
        sp = Span(other.name,
                  max(0.0, other.start_unix - primary.start_unix),
                  other.root.attrs)
        sp.duration_s = other.duration_s or 0.0
        if other.status is not None and other.status != "ok":
            sp.error = other.status
        sp.children = list(other.root.children)
        primary.root.children.append(sp)
        for s in other.slo_violations:
            if s not in primary.slo_violations:
                primary.slo_violations.append(s)

    def offer(self, trace):
        """Tail-sampling decision for one completed trace.  Returns the
        keep reason, or None when the trace was dropped.

        A trace whose id is ALREADY stored is a second segment of the
        same request (inherited ids, ``Tracer.start_trace(trace_id=)``):
        it is grafted into the stored tree — earliest segment becomes the
        root (the router hop starts before the replica hop) — instead of
        overwriting it, so `/tracez` shows one document for the whole
        routed request."""
        reason = self.keep_reason(trace)
        with self._lock:
            existing = self._traces.get(trace.trace_id)
            if existing is not None and existing is not trace:
                if trace.start_unix <= existing.start_unix:
                    primary, other = trace, existing
                else:
                    primary, other = existing, trace
                self._graft(primary, other)
                primary.sampled_reason = existing.sampled_reason
                self._traces[trace.trace_id] = primary
                return primary.sampled_reason
            if reason is None:
                if self.sample_every:
                    self._ok_seen += 1
                    if self._ok_seen % self.sample_every == 0:
                        reason = "tail"
                if reason is None:
                    self.dropped += 1
                    _M_DROPPED.inc()
                    _M_STORE_DEPTH.set(len(self._traces))
                    return None
            trace.sampled_reason = reason
            self._traces[trace.trace_id] = trace
            self.sampled += 1
            while len(self._traces) > self.capacity:
                self._traces.popitem(last=False)
                self.evicted += 1
                _M_EVICTED.inc()
            depth = len(self._traces)
        _M_SAMPLED.labels(reason=reason).inc()
        _M_STORE_DEPTH.set(depth)
        return reason

    # ------------------------------------------------------------ reading
    def get_trace(self, trace_id):
        with self._lock:
            return self._traces.get(str(trace_id))

    def get(self, trace_id):
        t = self.get_trace(trace_id)
        return t.to_dict() if t is not None else None

    def list(self, limit=100):
        """Newest-first summaries (the `/tracez` index payload)."""
        with self._lock:
            traces = list(self._traces.values())
        out = []
        for t in reversed(traces[-max(0, int(limit)):] if limit else traces):
            out.append({
                "trace_id": t.trace_id, "name": t.name, "status": t.status,
                "duration_s": round(t.duration_s, 6)
                if t.duration_s is not None else None,
                "start_unix": t.start_unix,
                "spans": t.root.span_count() - 1,
                "slo_violations": list(t.slo_violations),
                "sampled_reason": t.sampled_reason,
            })
        return out

    def stats(self):
        with self._lock:
            return {"stored": len(self._traces), "capacity": self.capacity,
                    "sample_every": self.sample_every,
                    "sampled": self.sampled, "dropped": self.dropped,
                    "evicted": self.evicted}

    def clear(self):
        with self._lock:
            self._traces.clear()
        _M_STORE_DEPTH.set(0)

    # ------------------------------------------------------------ dumping
    def trace_dicts(self):
        with self._lock:
            return [t.to_dict() for t in self._traces.values()]

    def dump_json(self, path):
        """Write every stored trace as one JSON document (atomic rename,
        like every other black-box artifact)."""
        doc = {"trace_store": 1, "stats": self.stats(),
               "traces": self.trace_dicts()}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, separators=(",", ":"), default=repr)
        os.replace(tmp, path)
        return path


class Tracer:
    """Trace factory + the disabled fast path.

    ``start_trace`` is the single entry point: one module-dict lookup when
    observability is disabled (returns :data:`NULL_TRACE`), otherwise a
    new :class:`Trace` whose ``end()`` offers it to ``store``.
    """

    def __init__(self, store=None, enabled=True):
        self.store = store if store is not None else TraceStore()
        self.enabled = bool(enabled)
        self._run = uuid.uuid4().hex[:8]  # distinguishes process restarts
        self._seq = 0
        self._seq_lock = threading.Lock()

    @property
    def started(self):
        """Traces started (== ids handed out; read under the same lock
        the id counter advances under, so concurrent submits can't skew
        the sampling-health numbers)."""
        with self._seq_lock:
            return self._seq

    def _next_id(self):
        with self._seq_lock:
            self._seq += 1
            return f"{self._run}-{self._seq:06x}"

    def start_trace(self, name, trace_id=None, **attrs):
        """``trace_id=None`` mints a fresh id; passing one adopts it (the
        replica side of a routed request inherits the router's id so the
        store can graft both segments into one tree)."""
        if not _metrics._runtime["enabled"] or not self.enabled:
            return NULL_TRACE
        _M_STARTED.inc()
        return Trace(self, name, attrs, trace_id=trace_id)

    def _finish(self, trace):
        if self.store is not None:
            self.store.offer(trace)

    def stats(self):
        """Sampling-health snapshot (``LLMEngine.stats()["tracing"]`` /
        `/varz`): started / sampled / dropped / store occupancy."""
        return {"started": self.started, **self.store.stats()}


#: Live stores, oldest first — the crash-dump sibling snapshots ALL of
#: them, so an engine running on an injected tracer still leaves its
#: request traces next to the black box.
_ALL_STORES: "weakref.WeakSet[TraceStore]" = weakref.WeakSet()
_STORE_SEQ = itertools.count()

#: Process-global store + tracer (mirrors metrics.REGISTRY /
#: flight_recorder.RECORDER): every built-in instrumentation point traces
#: here unless handed an explicit tracer.
TRACES = TraceStore()
TRACER = Tracer(store=TRACES)


def start_trace(name, **attrs):
    return TRACER.start_trace(name, **attrs)


def stats():
    return TRACER.stats()


def _dump_sibling(directory, reason, dumpno):
    """Flight-recorder sibling hook: every black box gets the retained
    traces of EVERY live store dumped next to it (crash forensics read
    both) — an engine on an injected tracer loses nothing."""
    stores = sorted(_ALL_STORES, key=lambda s: s._created_seq)
    traces, seen = [], set()
    for store in stores:
        for t in store.trace_dicts():
            if t["trace_id"] not in seen:
                seen.add(t["trace_id"])
                traces.append(t)
    if not traces:
        return
    doc = {"trace_store": 1, "stores": len(stores), "traces": traces}
    path = os.path.join(directory, f"traces_{reason}_{dumpno:04d}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, separators=(",", ":"), default=repr)
    os.replace(tmp, path)


_flight.register_sibling_dump(_dump_sibling)
