"""Unified observability layer: registry, spans, export, logging.

Covers the obs contracts the rest of the repo leans on:
  * registry correctness — bucketing, labeled series, concurrent
    increments, type collisions, weakref mirror lifetime;
  * disabled mode is a no-op (the default for every production run);
  * trace events are valid Chrome trace-event JSON and nest by time
    containment;
  * trace ids propagate through a real ``ScoringEngine.score_stream``
    call (admit -> score -> reassemble);
  * the JSONL telemetry emitter round-trips and rate-limits;
  * structured logging + warn-once suppression.
"""
import dataclasses
import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import export as obs_export
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry, OBS_KNOB


@pytest.fixture
def registry():
    return MetricsRegistry()


# the process-default rung, not OBS_KNOB.scoped: a ContextVar scope is
# invisible to worker threads (prefetch producer, the concurrency test),
# and the default is exactly what ScenarioSpec.apply() installs
@pytest.fixture
def metrics_on():
    state = OBS_KNOB.snapshot()
    OBS_KNOB.set_default("metrics")
    yield
    OBS_KNOB.restore(state)


@pytest.fixture
def trace_on():
    obs_trace.get_tracer().clear()
    state = OBS_KNOB.snapshot()
    OBS_KNOB.set_default("trace")
    yield
    OBS_KNOB.restore(state)
    obs_trace.get_tracer().clear()


class TestRegistry:
    def test_counter_and_labeled_series(self, registry, metrics_on):
        c = registry.counter("reqs")
        c.inc()
        c.inc(2)
        c.inc(5, site="a")
        c.inc(1, site="b")
        assert c.value() == 3
        assert c.value(site="a") == 5
        snap = registry.snapshot()["metrics"]["counters"]
        assert snap == {"reqs": 3, "reqs{site=a}": 5, "reqs{site=b}": 1}

    def test_gauge_last_write_wins(self, registry, metrics_on):
        g = registry.gauge("depth")
        g.set(3)
        g.set(7)
        assert g.value() == 7

    def test_histogram_bucketing(self, registry, metrics_on):
        h = registry.histogram("lat", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 0.9, 5.0, 50.0, 1e6):
            h.observe(v)
        snap = registry.snapshot()["metrics"]["histograms"]["lat"]
        assert snap["count"] == 5
        assert snap["buckets"] == {"le_1": 2, "le_10": 1, "le_100": 1}
        assert snap["overflow"] == 1
        assert snap["min"] == 0.5 and snap["max"] == 1e6
        assert h.quantile(0.5) == 10.0     # 3rd of 5 lands in the 10-bucket
        assert h.quantile(0.99) == 100.0   # overflow reports the ladder top

    def test_metric_type_collision_raises(self, registry):
        registry.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            registry.gauge("x")

    def test_concurrent_increments_lose_nothing(self, registry, metrics_on):
        c = registry.counter("n")
        h = registry.histogram("h")

        def work():
            for _ in range(2000):
                c.inc()
                h.observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 8 * 2000
        snap = registry.snapshot()["metrics"]["histograms"]["h"]
        assert snap["count"] == 8 * 2000
        assert snap["sum"] == pytest.approx(8 * 2000.0)

    def test_disabled_mode_records_nothing(self, registry):
        # default mode is off: gated records are dropped, ungated kept
        assert obs_metrics.mode() == "off"
        registry.counter("gated").inc(5)
        registry.histogram("lat").observe(1.0)
        registry.counter("always", gated=False).inc(2)
        m = registry.snapshot()["metrics"]
        assert m["counters"] == {"always": 2}
        assert m["histograms"] == {}

    def test_register_stats_weakref_lifetime(self, registry, metrics_on):
        class Stats:
            def snapshot(self):
                return {"n": 1}

        s = Stats()
        registry.register_stats("comp", s)
        assert registry.snapshot()["components"]["comp"] == {"n": 1}
        del s
        assert "comp" not in registry.snapshot()["components"]
        # callables are held strongly
        registry.register_stats("fn", lambda: {"k": 2})
        assert registry.snapshot()["components"]["fn"] == {"k": 2}

    def test_broken_mirror_does_not_kill_snapshot(self, registry):
        registry.register_stats("bad", lambda: 1 / 0)
        registry.counter("ok", gated=False).inc()
        snap = registry.snapshot()
        assert "error" in snap["components"]["bad"]
        assert snap["metrics"]["counters"]["ok"] == 1


class TestTrace:
    def test_disabled_span_is_shared_noop(self):
        assert obs_metrics.mode() == "off"
        s1, s2 = obs_trace.span("a"), obs_trace.span("b")
        assert s1 is s2                       # no allocation when off
        with s1:
            s1.set(k=1)                        # and args are swallowed
        obs_trace.instant("marker")
        assert obs_trace.get_tracer().events() == []

    def test_chrome_json_schema_and_nesting(self, trace_on, tmp_path):
        with obs_trace.span("outer", phase=1):
            with obs_trace.span("inner"):
                pass
            obs_trace.instant("mark", k="v")
        path = tmp_path / "trace.json"
        n = obs_trace.get_tracer().save(str(path))
        assert n == 3
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        evs = {e["name"]: e for e in doc["traceEvents"]}
        assert evs["process_name"]["ph"] == "M"
        outer, inner, mark = evs["outer"], evs["inner"], evs["mark"]
        for e in (outer, inner):
            assert e["ph"] == "X"
            assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
            assert e["dur"] >= 1
        assert mark["ph"] == "i" and mark["args"] == {"k": "v"}
        # nesting = time containment on one tid (how Perfetto renders it)
        assert inner["tid"] == outer["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["args"] == {"phase": 1}

    def test_span_feeds_duration_histogram(self, trace_on):
        with obs_trace.span("phase.x"):
            pass
        h = obs_metrics.REGISTRY.histogram("span.phase.x")
        assert h._series[()].count >= 1

    def test_buffer_overflow_counts_drops(self):
        tracer = obs_trace.Tracer(max_events=2)
        before = obs_metrics.REGISTRY.counter(
            "trace.dropped_events", gated=False).value()
        with OBS_KNOB.scoped("trace"):
            for _ in range(5):
                tracer.instant("e")
        assert len(tracer.events()) == 2
        after = obs_metrics.REGISTRY.counter(
            "trace.dropped_events", gated=False).value()
        assert after - before == 3

    def test_traced_decorator(self, trace_on):
        @obs_trace.traced("deco.fn")
        def f(x):
            return x + 1

        assert f(1) == 2
        assert any(e["name"] == "deco.fn"
                   for e in obs_trace.get_tracer().events())


def _profiled(logdir, fn):
    """Run ``fn`` under a ``jax.profiler`` session; the ``repro.*`` host
    events of its trace as (name, start_ns, end_ns, thread, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(logdir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name,
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(obs_trace.PROFILER_PREFIX)]


class TestProfilerBridge:
    def test_span_reaches_the_profiler_in_mode_off(self, tmp_path):
        assert obs_metrics.mode() == "off"

        def body():
            with obs_trace.span("phase.y", rows=3) as s:
                s.set(b_ro=4, note="x")
            obs_trace.instant("marker")
        events = _profiled(tmp_path, body)
        assert [(n, st) for n, *_, st in events] == [
            ("repro.phase.y", {"rows": 3, "b_ro": 4, "note": "x"})]
        # mode off: nothing enters the Chrome buffer or the histograms
        assert obs_trace.get_tracer().events() == []
        assert "span.phase.y" not in obs_metrics.snapshot()[
            "metrics"]["histograms"]

    def test_mode_trace_records_both(self, trace_on, tmp_path):
        def body():
            with obs_trace.span("phase.z", k=1):
                pass
        events = _profiled(tmp_path, body)
        assert [(n, st) for n, *_, st in events] == [
            ("repro.phase.z", {"k": 1})]
        chrome = obs_trace.get_tracer().events()
        assert [(e["name"], e["args"]) for e in chrome] == [
            ("phase.z", {"k": 1})]

    def test_no_session_no_mode_builds_nothing(self):
        assert obs_trace.span("a") is obs_trace.span("b")


def _mk_request(uid, item_ids):
    from repro.core.joiner import ROOSample
    return ROOSample(
        request_id=uid, user_id=uid,
        ro_dense=np.full((4,), float(uid), np.float32),
        ro_idlist=[uid % 7 + 1],
        history_ids=[1 + uid % 3, 2, 3], history_actions=[1, 0, 1],
        item_ids=[int(i) for i in item_ids],
        item_dense=[np.full((4,), float(i), np.float32) for i in item_ids],
        item_idlist=[[int(i) % 5 + 1] for i in item_ids],
        labels=[{"click": 0.0} for _ in item_ids])


class TestEngineTracePropagation:
    def test_trace_ids_thread_through_score_stream(self, trace_on):
        from repro.serve.engine import EnginePolicy, ScoringEngine
        engine = ScoringEngine(
            None, lambda p, b: b.item_ids.astype(jnp.float32),
            policy=EnginePolicy(max_requests=4, max_impressions=16))
        reqs = [_mk_request(i, list(range(1, 2 + i))) for i in range(6)]
        out = dict(engine.score_stream(reqs))
        assert len(out) == 6

        events = obs_trace.get_tracer().events()
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        admits = by_name["engine.admit"]
        assert len(admits) == 6
        admitted_ids = {e["args"]["trace_id"] for e in admits}
        assert len(admitted_ids) == 6          # unique id per request
        # every admitted id is carried by some scoring span ...
        scored_ids = set()
        for e in by_name["engine.score"]:
            scored_ids.update(e["args"]["trace_ids"])
        assert scored_ids == admitted_ids
        # ... and resolved exactly once at reassembly
        reassembled = [e["args"]["trace_id"]
                       for e in by_name["engine.reassemble"]]
        assert sorted(reassembled) == sorted(admitted_ids)
        # score spans nest inside their flush span
        flush = by_name["engine.flush"][0]
        score = by_name["engine.score"][0]
        assert flush["ts"] <= score["ts"]
        assert score["ts"] + score["dur"] <= flush["ts"] + flush["dur"]

    def test_online_flush_spans_under_the_profiler(self, tmp_path):
        """submit/poll under the profiler: the flush args agree with
        ``EngineStats``, queueing is measured from each submit on the
        engine's clock, and each fetch nests in its flush."""
        from collections import Counter

        from repro.serve.engine import EnginePolicy, ScoringEngine
        now = [0.0]
        engine = ScoringEngine(
            None, lambda p, b: b.item_ids.astype(jnp.float32),
            policy=EnginePolicy(max_requests=4, max_impressions=16,
                                max_delay_ms=5.0),
            clock=lambda: now[0])
        reqs = [_mk_request(i, list(range(1, 2 + i % 3))) for i in range(6)]

        def body():
            tickets = []
            for i, r in enumerate(reqs):
                now[0] = i * 1e-3
                tickets.append(engine.submit(r))
            now[0] = 0.010
            assert engine.poll()
            assert all(engine.take(t) is not None for t in tickets)
        events = _profiled(tmp_path, body)
        flushes = [e for e in events if e[0] == "repro.engine.flush"]
        fetches = [e for e in events if e[0] == "repro.engine.fetch"]
        stats = engine.stats.snapshot()
        args = [e[4] for e in flushes]
        assert sum(a["requests"] for a in args) == stats["n_requests"] == 6
        assert sum(a["impressions"] for a in args) == stats["n_impressions"]
        assert dict(Counter(f"{a['b_ro']}x{a['b_nro']}" for a in args)) \
            == stats["buckets"]["counts"]
        assert len(fetches) == stats["n_batches"] == len(flushes) == 2
        for name, s, e, line, _ in fetches:
            assert any(fs <= s and e <= fe and fl == line
                       for _, fs, fe, fl, _ in flushes)
        # every submit read the clock: 10 ms less each submit time
        queued = [10.0 - i for i in range(6)]
        assert sum(a["queued_ms_sum"] for a in args) \
            == pytest.approx(sum(queued))
        assert max(a["queued_ms_max"] for a in args) == pytest.approx(10.0)
        assert all(a["queued_ms_sum"] >= 0 for a in args)
        # three history events a row, hist_len 64 slots a row
        assert [a["history_events"] for a in args] == [
            3 * a["requests"] for a in args]
        assert [a["history_slots"] for a in args] == [
            a["b_ro"] * 64 for a in args]

    def test_bulk_flush_carries_no_queueing(self, tmp_path):
        from repro.serve.engine import EnginePolicy, ScoringEngine
        engine = ScoringEngine(
            None, lambda p, b: b.item_ids.astype(jnp.float32),
            policy=EnginePolicy(max_requests=4, max_impressions=16))
        reqs = [_mk_request(i, [1, 2]) for i in range(3)]
        events = _profiled(tmp_path, lambda: engine.score_requests(reqs))
        flush, = [e[4] for e in events if e[0] == "repro.engine.flush"]
        assert flush["requests"] == 3 and "queued_ms_sum" not in flush
        names = {e[0] for e in events}
        assert {"repro.engine.bucket", "repro.engine.score",
                "repro.engine.dispatch", "repro.engine.fetch"} <= names

    def test_flush_counts_the_attention_tiles(self, tmp_path):
        """A model running the fused HSTU kernel: each flush carries the
        tiles the kernel computes (``attn_tiles_live``) and its dense grid
        (``attn_tiles_grid``), counted from the rows' lengths; the live
        count equals the tiles of the dense ROO mask that hold a true cell,
        over rows, heads and layers. A second engine around the same
        function records its own grids."""
        from repro.core.masks import roo_batch_mask
        from repro.kernels.hstu_attention import hstu_attention
        from repro.serve.engine import EnginePolicy, ScoringEngine
        n_hist, m, h, blk, layers = 64, 4, 2, 16, 2

        def score(p, b):
            x = jnp.ones((b.b_ro, h, n_hist + m, 8))
            for _ in range(layers):
                x = hstu_attention(x, x, x, None, n_hist, b.history_lengths,
                                   b.num_impressions, block_q=blk,
                                   block_k=blk)
            return b.item_ids.astype(jnp.float32) + 0.0 * x.sum()

        hist = [0, 3, 17, 40]
        reqs = [dataclasses.replace(
            _mk_request(i, list(range(1, 2 + i % 3))),
            history_ids=list(range(1, 1 + n)),
            history_actions=[0] * n) for i, n in enumerate(hist)]
        # the dense mask over the padded lattice, per 16 x 16 tile
        s_pad = 80
        hl = jnp.asarray(hist)
        tc = jnp.asarray([r.num_impressions for r in reqs])
        dense = np.asarray(roo_batch_mask(hl, tc, n_hist, s_pad - n_hist))
        live = dense.reshape(4, 5, blk, 5, blk).any(axis=(2, 4)).sum()
        for _ in range(2):
            engine = ScoringEngine(None, score, policy=EnginePolicy(
                max_requests=4, max_impressions=16, hist_len=n_hist))
            events = _profiled(tmp_path / str(_),
                               lambda: engine.score_requests(reqs))
            flush, = [e[4] for e in events if e[0] == "repro.engine.flush"]
            assert flush["b_ro"] == 4
            assert flush["attn_tiles_grid"] == layers * h * 4 * 5 * 5
            assert flush["attn_tiles_live"] == layers * h * live
            assert flush["attn_tiles_live"] < flush["attn_tiles_grid"]

    def test_one_snapshot_sees_the_whole_stack(self, metrics_on):
        # the tentpole contract: serving + pipeline + training +
        # reliability state all hang off one obs.snapshot() call
        from repro.pipeline.joiner import WatermarkJoiner
        from repro.serve.engine import ScoringEngine
        from repro.train.loop import Trainer, TrainLoopConfig
        from repro.train.optim import adam

        engine = ScoringEngine(
            None, lambda p, b: b.item_ids.astype(jnp.float32))
        ticket = engine.submit(_mk_request(0, [1, 2, 3]))
        engine.flush()
        assert engine.take(ticket) is not None
        joiner = WatermarkJoiner()
        trainer = Trainer(
            lambda p, b, r: jnp.sum(p["w"] * b),
            adam(1e-2), TrainLoopConfig(total_steps=1, log_every=1),
            lambda: {"w": jnp.ones((2,))})
        trainer.run(lambda s: iter([jnp.ones((2,))]),
                    __import__("jax").random.PRNGKey(0))

        snap = obs_metrics.snapshot()
        comps = snap["components"]
        assert comps["serve.engine"]["stats"]["n_requests"] == 1
        assert "pipeline.join" in comps
        assert comps["train"]["last_step"] == 1
        assert comps["reliability.faults"] == {"active": False}
        assert snap["metrics"]["histograms"][
            "engine.request_ms"]["count"] == 1
        del joiner


class TestEmitter:
    def test_jsonl_round_trip(self, metrics_on, tmp_path):
        obs_metrics.counter("emit.test").inc(3)
        path = tmp_path / "t.jsonl"
        with obs_export.TelemetryEmitter(str(path),
                                         scenario_hash="abc123") as em:
            assert em.maybe_emit("unit")
        lines = [json.loads(x) for x in path.read_text().splitlines()]
        assert len(lines) == 2                  # unit + shutdown
        assert [x["source"] for x in lines] == ["unit", "shutdown"]
        for x in lines:
            assert x["scenario_hash"] == "abc123"
            assert x["elapsed_s"] >= 0
            assert x["snapshot"]["metrics"]["counters"][
                "emit.test"] == 3

    def test_rate_limit(self, tmp_path):
        t = [0.0]
        em = obs_export.TelemetryEmitter(str(tmp_path / "t.jsonl"),
                                         every_s=10.0, clock=lambda: t[0])
        assert em.maybe_emit("a")
        t[0] = 5.0
        assert not em.maybe_emit("b")           # inside the window
        t[0] = 10.0
        assert em.maybe_emit("c")
        em.close(final_source=None)
        assert em.n_emitted == 2

    def test_module_install_point(self, tmp_path):
        assert not obs_export.maybe_emit("x")   # no emitter: cheap no-op
        em = obs_export.TelemetryEmitter(str(tmp_path / "t.jsonl"))
        prev = obs_export.install(em)
        try:
            assert prev is None
            assert obs_export.maybe_emit("x")
        finally:
            obs_export.install(prev)
            em.close()

    def test_report_summarizes(self, metrics_on, tmp_path, capsys):
        from repro.obs import report
        obs_metrics.histogram("span.demo").observe(2.0)
        path = tmp_path / "t.jsonl"
        with obs_export.TelemetryEmitter(str(path)) as em:
            em.emit("a")
        report.main([str(path)])
        out = capsys.readouterr().out
        assert "span.demo" in out and "p99" in out


class TestLogging:
    def test_structured_line(self, capsys):
        log = obs_log.get_logger("demo")
        log.info("event", step=3, loss=0.5, msg="two words")
        assert capsys.readouterr().out == \
            "[demo] event step=3 loss=0.5 msg='two words'\n"

    def test_disabled_logger_keeps_errors(self, capsys):
        log = obs_log.get_logger("quiet", enabled=False)
        log.info("hidden")
        log.error("boom", code=1)
        cap = capsys.readouterr()
        assert cap.out == ""
        assert "[quiet] boom code=1" in cap.err

    def test_verbosity_gates_debug(self, capsys):
        log = obs_log.get_logger("v")
        log.debug("nope")                       # default verbosity 1 < DEBUG
        assert capsys.readouterr().out == ""
        with obs_log.VERBOSITY_KNOB.scoped(2):
            log.debug("yes")
        assert "[v] yes" in capsys.readouterr().out

    def test_warn_once_suppresses_and_counts(self):
        key = "test_obs.warn_once.unit"
        obs_log.reset_warn_once(key)
        c = obs_metrics.REGISTRY.counter("warnings_suppressed", gated=False)
        before = c.value(key=key)
        with pytest.warns(UserWarning, match="first"):
            assert obs_log.warn_once(key, "first time")
        assert not obs_log.warn_once(key, "second time")   # no warning
        assert c.value(key=key) - before == 1
