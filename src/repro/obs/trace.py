"""Span tracing on the profiler's clock and in a Chrome trace-event buffer.

A *span* is one timed phase — ``with span("engine.score", trace_id=7):``
or ``@traced("train.step")``. Spans on the same thread nest by time
containment, which is how Perfetto, chrome://tracing and the JAX profiler
render call trees, so the engine's ``engine.flush > engine.bucket /
engine.score`` and the trainer's ``train.step > train.data /
train.compute`` show up as nested bars with no parent-pointer bookkeeping
on the record path.

Each span goes to up to two places:

  * **the profiler** — while a ``jax.profiler`` session records, every
    span also opens a ``jax.profiler.TraceAnnotation("repro." + name,
    **args)``, whatever the obs mode. It lands on the host plane of the
    profiler's trace, on the clock of the device's ops, with its args as
    the event's stats; ``_Span.set`` forwards to ``set_metadata``. Whoever
    starts the profiler gets the program's phases next to the device
    timeline.
  * **the Chrome buffer** — when the obs mode is ``trace`` (knob ladder,
    see ``repro.obs.metrics``), a Chrome *complete* event (``ph: "X"``,
    microsecond ``ts``/``dur`` from ``perf_counter_ns``) and the metrics
    histogram ``span.<name>`` (milliseconds), which is what
    ``repro.obs.report`` derives per-phase rates/p50/p99 from.

Trace IDs: the engine stamps every admitted request with an id from
:func:`new_trace_id` and threads it through the span ``args`` of every
phase that touches the request (admission -> bucket -> score ->
reassembly), so a p99 request found in the trace can be followed across
batches — including requests split over several batches.

Cost: with the mode not ``trace`` and no profiler session recording,
:func:`span` returns a shared no-op context manager — one knob resolve
and one ``TraceAnnotation.is_enabled()`` check, no allocation.
:func:`instant` is Chrome-only: the profiler has no instants, so what an
instant records that the profiler should see rides a span's args. The
event buffer is bounded (``max_events``); overflow drops new events and
counts them in the ``trace.dropped_events`` counter instead of growing
without bound.
"""
from __future__ import annotations

import functools
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs import metrics

# process-unique, thread-safe request/trace id source (itertools.count is
# atomic under the GIL)
_TRACE_IDS = itertools.count(1)

# every span's name on the profiler's trace starts with this
PROFILER_PREFIX = "repro."


def new_trace_id() -> int:
    return next(_TRACE_IDS)


def tracing_enabled() -> bool:
    return metrics.mode() == "trace"


class _NullSpan:
    """Shared no-op context manager — the disabled-mode fast path."""
    __slots__ = ()
    recording = False      # args set on it go nowhere

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One open phase: a profiler annotation while a session records
    (``profiled``), a Chrome event when the mode is ``trace`` (``tracer``
    set), or both."""
    __slots__ = ("_tracer", "name", "cat", "args", "_profiled",
                 "_annotation", "_t0")
    recording = True

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 args: Dict[str, Any], profiled: bool):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._profiled = profiled
        self._annotation = None

    def set(self, **args) -> None:
        """Attach/overwrite args while the span is open."""
        self.args.update(args)
        if self._annotation is not None:
            self._annotation.set_metadata(**args)

    def __enter__(self) -> "_Span":
        # a TraceAnnotation starts timing when it is built
        if self._profiled:
            self._annotation = TraceAnnotation(PROFILER_PREFIX + self.name,
                                               **self.args)
        if self._tracer is not None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self._tracer is not None:
            dur_ns = time.perf_counter_ns() - self._t0
            self._tracer._record_complete(self.name, self.cat, self._t0,
                                          dur_ns, self.args)
            metrics.histogram("span." + self.name).observe(dur_ns / 1e6)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class Tracer:
    """Bounded, thread-safe buffer of Chrome trace events."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()

    # -- recording --------------------------------------------------------------
    def span(self, name: str, cat: str = "repro", **args):
        """Context manager timing one phase: a profiler event while a
        profiler session records, a Chrome event when mode=trace, the
        shared no-op when neither."""
        record = tracing_enabled()
        profiled = TraceAnnotation.is_enabled()
        if not (record or profiled):
            return _NULL_SPAN
        return _Span(self if record else None, name, cat, args, profiled)

    def instant(self, name: str, cat: str = "repro", **args) -> None:
        """Zero-duration marker (e.g. per-request admission); Chrome
        buffer only, for the profiler has no instants."""
        if not tracing_enabled():
            return
        self._push({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": time.perf_counter_ns() // 1000,
                    "pid": self._pid, "tid": threading.get_ident(),
                    "args": args})

    def _record_complete(self, name: str, cat: str, t0_ns: int,
                         dur_ns: int, args: Dict[str, Any]) -> None:
        self._push({"name": name, "cat": cat, "ph": "X",
                    "ts": t0_ns // 1000, "dur": max(dur_ns // 1000, 1),
                    "pid": self._pid, "tid": threading.get_ident(),
                    "args": args})

    def _push(self, event: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                metrics.counter("trace.dropped_events", gated=False).inc()
                return
            self._events.append(event)

    # -- export -----------------------------------------------------------------
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable)."""
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": "repro"}}]
        return {"traceEvents": meta + self.events(),
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> int:
        """Write the trace; returns the number of (non-meta) events."""
        events = self.to_chrome()
        with open(path, "w") as f:
            json.dump(events, f)
        return len(events["traceEvents"]) - 1


# the process tracer every instrumented module records into
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, cat: str = "repro", **args):
    return _TRACER.span(name, cat, **args)


def instant(name: str, cat: str = "repro", **args) -> None:
    _TRACER.instant(name, cat, **args)


def traced(name: Optional[str] = None, cat: str = "repro"):
    """Decorator form: time every call of ``fn`` as a span."""
    def deco(fn):
        span_name = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with _TRACER.span(span_name, cat):
                return fn(*a, **kw)
        return wrapper
    return deco
