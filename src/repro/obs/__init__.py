"""Unified observability layer: metrics registry, span tracing, telemetry.

Before this package, seven subsystems each invented their own telemetry
(``EngineStats``, ``LoaderStats``, ``JoinStats``, ``FaultStats``,
``BatcherStats``, ``CacheStats``, ``BucketStats``, the Trainer's
``history``) with no common registry, no time dimension, and no way to
attribute a p99 request or a slow step to a phase. This package is the
measurement substrate they all report into:

  * :mod:`repro.obs.metrics` — process-wide registry of counters, gauges
    and histograms (labeled series, fixed bucket ladders, lock-cheap
    record path) plus *collectors* that mirror every existing ``*Stats``
    object, so one :func:`snapshot` sees the whole stack;
  * :mod:`repro.obs.trace` — context-manager/decorator spans with
    per-request trace IDs, on the profiler's clock (``repro.<name>`` host
    events next to the device timeline) whenever a ``jax.profiler``
    session records, and exported as Chrome trace-event JSON (loadable
    in Perfetto / chrome://tracing) when the mode is ``trace``;
  * :mod:`repro.obs.export` — periodic JSONL telemetry snapshots stamped
    with the scenario ``content_hash``; ``python -m repro.obs.report``
    summarizes a run file into per-phase rates/p50/p99;
  * :mod:`repro.obs.log` — the shared structured logger (one parseable
    line per event, verbosity knob) and ``warn_once`` rate-limiting for
    repeated ``warnings.warn`` sites.

Enablement rides the shared knob ladder (``scenario/knobs.py``): the
``obs`` knob resolves ``off | metrics | trace`` from an explicit arg >
``ScenarioSpec.obs.mode`` > ``REPRO_OBS`` > auto(off). When off, every
record-path hook is a single predicate check — hot paths (kernel
dispatch, per-row scoring) are unaffected (benchmarks/obs_bench.py gates
this). ``snapshot()`` is an explicit pull and always works: the ``*Stats``
mirrors don't depend on the mode. See docs/OBSERVABILITY.md.
"""
from repro.obs import export, log, metrics, trace  # noqa: F401
from repro.obs.metrics import (REGISTRY, metrics_enabled, mode,  # noqa: F401
                               register_stats, snapshot)
from repro.obs.trace import get_tracer, span, tracing_enabled  # noqa: F401

__all__ = ["REGISTRY", "snapshot", "register_stats", "mode",
           "metrics_enabled", "tracing_enabled", "get_tracer", "span",
           "metrics", "trace", "export", "log"]
