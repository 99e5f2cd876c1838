"""The reduction of the program's ``repro.*`` spans against the device
timeline: on a hand-built trace whose every number is known, and on a short
serving window recorded on a TPU v5e at a tiny size."""
import os

import pytest

from chipbench import program_spans as ps
from chipbench import trace

MS = 1e6          # ns


def _op(a, b):
    return trace.Op("%fusion.1 = f32[8] fusion()", a * MS, b * MS)


def _span(name, a, b, **args):
    return ps.Span("repro." + name, a * MS, b * MS, "python", args)


@pytest.fixture
def hand():
    """A 1 s window; the device busy over [100, 300] and [500, 600] ms; two
    flushes, the first with every child span."""
    raw = trace.Raw({0: [_op(100, 300), _op(500, 600)]},
                    [("chipbench.window", 0.0, 1000 * MS)])
    spans = [
        _span("engine.flush", 50, 400, requests=2, impressions=5,
              queued_ms_sum=6.0, queued_ms_max=4.0, history_events=30,
              history_slots=128),
        _span("engine.bucket", 50, 90),
        _span("engine.score", 90, 390),
        _span("engine.dispatch", 90, 110),
        _span("engine.fetch", 110, 390),
        _span("engine.flush", 450, 700, requests=1, impressions=1,
              queued_ms_sum=2.0, queued_ms_max=2.0, history_events=10,
              history_slots=64),
        _span("engine.fetch", 460, 690),
        _span("engine.flush", 1100, 1200, requests=9),   # after the window
    ]
    return raw, spans


def test_idle_goes_to_the_innermost_open_span(hand):
    red = ps.reduce(*hand)
    assert red.window_s == pytest.approx(1.0)
    assert len(red.spans) == 7
    want = {ps.OUTSIDE: 0.400, "repro.engine.bucket": 0.040,
            "repro.engine.dispatch": 0.010, "repro.engine.fetch": 0.220,
            "repro.engine.flush": 0.030}
    assert red.idle_in == pytest.approx(want)
    # every idle second is assigned once: the window less the busy time
    s = trace.reduce(hand[0])
    assert sum(red.idle_in.values()) == pytest.approx(s.window_s - s.busy_s)


def test_engine_numbers(hand):
    got = ps.engine(ps.reduce(*hand))
    assert got == pytest.approx({
        "engine_queue_ms": 8.0 / 3,            # (6 + 2) ms over 3 requests
        "engine_host_ms": (70.0 + 20.0) / 2,   # flush less fetch, each
        "engine_idle_share": 30.0,             # 300 ms inside flushes
        "serve_history_fill": 100.0 * 40 / 192})


def test_bulk_flushes_give_no_queue_and_a_program_without_spans_nothing(hand):
    raw, spans = hand
    bulk = [ps.Span(s.name, s.start, s.end, s.thread,
                    {k: v for k, v in s.args.items()
                     if not k.startswith("queued")}) for s in spans]
    assert "engine_queue_ms" not in ps.engine(ps.reduce(raw, bulk))
    assert ps.engine(ps.reduce(raw, [])) == {}
    red = ps.reduce(raw, [])
    assert red.idle_in == pytest.approx({ps.OUTSIDE: 0.7})


def test_reduce_needs_a_window(hand):
    with pytest.raises(ValueError):
        ps.reduce(trace.Raw(hand[0].devices, []), hand[1])


# recorded on a TPU v5e: three bursts of requests through submit/poll at
# the tiny size (the tests' cell, one 4x16 rung), each flushed by the 5 ms
# deadline, 5 ms apart; the profiler's Python tracer off, and the
# /host:metadata plane (HLO protos, read by nothing here) dropped for size
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture_engine.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load_file(FIXTURE), ps.load_spans(FIXTURE)


def test_fixture_spans_nest_and_carry_the_flush_args(recorded):
    raw, spans = recorded
    red = ps.reduce(raw, spans)
    names = [s.name for s in red.spans]
    for what in ("flush", "bucket", "score", "dispatch", "fetch"):
        assert names.count("repro.engine." + what) == 3
    flushes = [s for s in red.spans if s.name == ps.FLUSH]
    assert [s.args["requests"] for s in flushes] == [2, 3, 1]
    assert [s.args["impressions"] for s in flushes] == [3, 8, 2]
    # histories of 60, 20 | 64, 5, 33 | 40 events, hist_len 64
    assert [s.args["history_events"] for s in flushes] == [80, 102, 40]
    assert [s.args["history_slots"] for s in flushes] == [256] * 3
    assert all(s.args["queued_ms_max"] >= 5.0 for s in flushes)
    for child in red.spans:
        if child.name != ps.FLUSH:
            assert any(f.start <= child.start and child.end <= f.end
                       and f.thread == child.thread for f in flushes)


def test_fixture_idle_and_engine_numbers(recorded):
    raw, spans = recorded
    red = ps.reduce(raw, spans)
    s = trace.reduce(raw)
    assert red.window_s == pytest.approx(s.window_s)
    assert sum(red.idle_in.values()) == pytest.approx(s.window_s - s.busy_s,
                                                      rel=1e-9)
    assert set(red.idle_in) <= {ps.OUTSIDE} | {
        "repro.engine." + w
        for w in ("flush", "bucket", "score", "dispatch", "fetch")}
    got = ps.engine(red)
    flushes = [x.args for x in red.spans if x.name == ps.FLUSH]
    assert got["engine_queue_ms"] == pytest.approx(
        sum(a["queued_ms_sum"] for a in flushes) / 6)
    assert got["serve_history_fill"] == pytest.approx(100.0 * 222 / 768)
    assert 0 < got["engine_host_ms"] < 1e3 * red.window_s
    engine_idle = sum(v for k, v in red.idle_in.items() if k != ps.OUTSIDE)
    assert got["engine_idle_share"] == pytest.approx(
        100.0 * engine_idle / red.window_s)
    # the tiny model's device work is a sliver of the window
    assert 0 < got["engine_idle_share"] < 100.0 * (1 - s.busy_s / s.window_s)


def test_fixture_names_the_kernel(recorded):
    assert [k for k, _ in trace.reduce(recorded[0]).device_ops(2)] == [
        "hstu_attn_fwd.2", "hstu_attn_fwd.3"]
