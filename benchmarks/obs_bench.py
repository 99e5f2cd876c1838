"""Observability benchmark: what instrumentation costs at each mode.

The obs layer's contract is that a disabled record path is near-free —
every hot loop in the repo (engine flush, prefetch producer, train step)
is instrumented unconditionally and relies on it. These rows measure that
contract directly:

  obs_record_off     — a 10k-op block of counter.inc + histogram.observe
                       with obs OFF: the gated early-return path every
                       production run pays. Gated in the baseline — a
                       regression here taxes every subsystem at once.
  obs_record_metrics — the same block with obs=metrics (locked record).
  obs_span_trace     — a 1k-span block under obs=trace (span open/close,
                       event append + duration histogram).
  obs_span_profiled  — a 1k-span block with an engine flush's args, obs
                       off, while a ``jax.profiler`` session records: the
                       profiler bridge (one ``TraceAnnotation`` a span).
  obs_emit           — one TelemetryEmitter.emit() of a populated
                       registry snapshot to a JSONL line on disk.

Only ``obs_record_off`` is in the perf-gate baseline; the enabled-mode
rows are informational (compare.py ignores rows absent from baseline).
"""
from __future__ import annotations

import os
import tempfile
import time

from benchmarks.common import emit

RECORD_OPS = 10_000
SPAN_OPS = 1_000


def _best(fn, iters: int = 7) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _record_block(mode_value: str) -> float:
    from repro.obs import metrics
    c = metrics.counter("obs_bench.counter")
    h = metrics.histogram("obs_bench.hist")

    def block():
        inc, observe = c.inc, h.observe
        for i in range(RECORD_OPS):
            inc()
            observe(0.25)

    with metrics.OBS_KNOB.scoped(mode_value):
        return _best(block)


def _span_block() -> float:
    from repro.obs import metrics, trace

    def block():
        span = trace.span
        for _ in range(SPAN_OPS):
            with span("obs_bench.span"):
                pass

    with metrics.OBS_KNOB.scoped("trace"):
        us = _best(block)
    trace.get_tracer().clear()
    return us


def _profiled_span_block(tmp: str) -> float:
    import jax

    from repro.obs import trace
    args = dict(requests=3, impressions=12, b_ro=4, b_nro=32,
                history_events=4800, queued_ms_sum=12.5, queued_ms_max=5.1)

    def block():
        span = trace.span
        for _ in range(SPAN_OPS):
            with span("obs_bench.flush", **args):
                pass

    jax.profiler.start_trace(os.path.join(tmp, "profile"))
    try:
        return _best(block)
    finally:
        jax.profiler.stop_trace()


def _emit_once(tmp: str) -> float:
    from repro.obs import export, metrics
    with metrics.OBS_KNOB.scoped("metrics"):
        for i in range(64):
            metrics.counter("obs_bench.fan").inc(site=str(i))
            metrics.histogram("obs_bench.lat").observe(float(i))
        with export.TelemetryEmitter(os.path.join(tmp, "t.jsonl"),
                                     scenario_hash="bench") as em:
            return _best(lambda: em.emit("bench"))


def run(smoke: bool = False) -> None:
    off_us = _record_block("off")
    on_us = _record_block("metrics")
    per_op_off_ns = off_us * 1e3 / (2 * RECORD_OPS)
    per_op_on_ns = on_us * 1e3 / (2 * RECORD_OPS)
    emit("obs_record_off", off_us,
         f"ops={2 * RECORD_OPS};ns_per_op={per_op_off_ns:.0f}")
    emit("obs_record_metrics", on_us,
         f"ops={2 * RECORD_OPS};ns_per_op={per_op_on_ns:.0f};"
         f"vs_off_x={on_us / max(off_us, 1e-9):.2f}")

    span_us = _span_block()
    emit("obs_span_trace", span_us,
         f"spans={SPAN_OPS};us_per_span={span_us / SPAN_OPS:.2f}")

    tmp = tempfile.mkdtemp(prefix="roo_obs_bench_")
    try:
        emit_us = _emit_once(tmp)
        emit("obs_emit", emit_us, f"series=128;us_per_line={emit_us:.0f}")
        prof_us = _profiled_span_block(tmp)
        emit("obs_span_profiled", prof_us,
             f"spans={SPAN_OPS};us_per_span={prof_us / SPAN_OPS:.2f}")
    finally:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    run(smoke="--smoke" in __import__("sys").argv[1:])
