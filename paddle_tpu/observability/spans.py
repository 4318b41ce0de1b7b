"""Structured spans: ONE instrumentation point lands in both sinks.

``with span("checkpoint_save", histogram=H):`` opens a
``profiler.RecordEvent`` (native host-trace buffer -> chrome://tracing
export, plus a jax TraceAnnotation -> XPlane timeline) and, on exit,
observes the wall-clock duration into ``histogram`` and bumps ``counter``.
Metrics and traces therefore always agree on what a "checkpoint_save" is —
the correlation the README's Observability section documents.

``metrics.disable()`` turns spans into no-ops too (one dict lookup on
enter), so instrumented hot paths stay benchmark-clean.

A span times the HOST between enter and exit.  Around an asynchronous
device call (``llm_prefill_chunk`` around the chunk program) that is the
call's dispatch, not the device's work: the wait shows wherever the host
next reads a result (the pump's ``first_token_sync`` or ``decode_sync``
phase).

:class:`PhaseClock` is the span's cheaper sibling for the INSIDE of a
loop body: mutually exclusive phases that exhaust one tick, switched on a
single clock read each, accumulated per owner and written to the
profiler's timeline as bare ``TraceAnnotation`` s (no histogram, no native
host-trace buffer, no flight-recorder event per phase).

Every span close also lands one structured event in the process-global
flight recorder (``observability.flight_recorder``): after a crash the
black-box dump shows WHICH span was running and how long it had been —
the per-event cost is one bounded deque append.
"""
from __future__ import annotations

import time

from . import metrics as _metrics
from . import flight_recorder as _flight

__all__ = ["span", "PhaseClock"]

_record_event_cls = None


def _record_event(name):
    """profiler.RecordEvent, imported lazily (profiler drags in jax; the
    metrics registry itself must stay dependency-free)."""
    global _record_event_cls
    if _record_event_cls is None:
        try:
            from ..profiler import RecordEvent
            _record_event_cls = RecordEvent
        except Exception:
            _record_event_cls = False
    return _record_event_cls(name) if _record_event_cls else None


class span:
    """Context manager: trace span + latency histogram + event counter.

    ``trace`` (a ``tracing.Trace``, or the falsy ``NULL_TRACE``) extends
    the single instrumentation point to the request-scoped sinks: the
    span joins the trace's tree (with ``attrs``), the flight-recorder
    event carries the ``trace_id``, and the histogram observation carries
    it as an OpenMetrics exemplar — metrics, black box and span tree all
    name the same request.
    """

    __slots__ = ("name", "histogram", "counter", "trace", "attrs",
                 "_t0", "_ev", "_tspan", "duration")

    def __init__(self, name, histogram=None, counter=None, trace=None,
                 attrs=None):
        self.name = name
        self.histogram = histogram
        self.counter = counter
        self.trace = trace if trace else None  # NULL_TRACE is falsy
        self.attrs = attrs
        self._t0 = None
        self._ev = None
        self._tspan = None
        self.duration = None

    def __enter__(self):
        if not _metrics._runtime["enabled"]:
            return self
        self._ev = _record_event(self.name)
        if self._ev is not None:
            self._ev.__enter__()
        if self.trace is not None:
            self._tspan = self.trace.span(
                self.name, **(self.attrs or {})).open()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            self.duration = time.perf_counter() - self._t0
            self._t0 = None
            if self._ev is not None:
                self._ev.__exit__(None, None, None)
                self._ev = None
            err = repr(exc[1]) if exc and exc[0] is not None else None
            if self._tspan is not None:
                self._tspan.close(error=err)
                self._tspan = None
            if self.histogram is not None:
                self.histogram.observe(
                    self.duration,
                    exemplar=self.trace.trace_id
                    if self.trace is not None else None)
            if self.counter is not None:
                self.counter.inc()
            fields = {"name": self.name, "duration_s": self.duration}
            if self.trace is not None:
                fields["trace_id"] = self.trace.trace_id
            if err is not None:
                # a span unwound by an exception is exactly the event a
                # postmortem wants last in the black box
                fields["error"] = err
            _flight.record_event("span", **fields)
        return False


class PhaseClock:
    """Exclusive phases of one loop tick, on the profiler's clock.

    The owning thread calls ``begin()`` once a tick, ``switch(phase)`` at
    every boundary and ``end()`` when the tick is over.  ``switch`` closes
    the running phase and opens the next on ONE ``time.perf_counter()``
    read, so phases never overlap and their seconds add up to the time
    between the first ``switch`` and ``end``.  Each phase adds to
    ``seconds[phase]`` / ``count[phase]`` (cumulative since construction;
    plain dicts a monitoring thread may read without a lock) and is a
    ``jax.profiler.TraceAnnotation`` named ``<prefix>.<phase>``, which
    nests inside whatever annotation the caller holds open (the engine's
    ``llm_decode_tick`` span), in the same XPlane host line as the device
    trace's launches.

    ``switch`` and ``end`` return their clock read (0.0 while off), so the
    caller derives other timings from the same boundaries instead of
    reading the clock again.  ``begin()`` does the one
    ``metrics.enabled()`` dict lookup of the tick; while off, a switch is
    an attribute test.  Phases are fixed at construction: an unknown name
    is a ``KeyError``, not a new series.
    """

    __slots__ = ("seconds", "count", "_names", "_on", "_phase", "_t0",
                 "_ann", "_ann_cls")

    def __init__(self, prefix, phases):
        self._names = {p: f"{prefix}.{p}" for p in phases}
        self.seconds = {p: 0.0 for p in phases}
        self.count = {p: 0 for p in phases}
        self._on = False
        self._phase = None
        self._t0 = 0.0
        self._ann = None
        self._ann_cls = None

    def begin(self):
        self._on = _metrics._runtime["enabled"]
        if self._on and self._ann_cls is None:
            # lazily, like RecordEvent: the registry stays importable
            # without jax
            try:
                from jax.profiler import TraceAnnotation
                self._ann_cls = TraceAnnotation
            except Exception:
                self._ann_cls = False

    def switch(self, phase):
        if not self._on:
            return 0.0
        name = self._names[phase]  # KeyError: not a phase of this clock
        now = time.perf_counter()
        if self._phase is not None:
            self._close(now)
        self.count[phase] += 1
        self._phase = phase
        self._t0 = now
        if self._ann_cls:
            self._ann = self._ann_cls(name)
            self._ann.__enter__()
        return now

    def end(self):
        """Close the running phase (also on the way out of a tick that
        raised: a phase must not stay open across ticks)."""
        self._on = False
        if self._phase is None:
            return 0.0
        now = time.perf_counter()
        self._close(now)
        return now

    def _close(self, now):
        self.seconds[self._phase] += now - self._t0
        self._phase = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
