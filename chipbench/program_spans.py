"""Reduces the program's own spans on a profiler trace of a serving window:
the host events named ``repro.*`` that ``repro.obs.trace`` writes whenever
a profiler session records, each with its args as the event's stats, set
against the device timeline that ``chipbench.trace`` reads.

``reduce`` keeps the program spans inside the ``chipbench.window`` span and
assigns the device's idle time in the window to the innermost program span
open during it (``idle_in``). ``engine`` turns those into the serving
engine's numbers:

- ``engine_queue_ms``: mean wait inside the engine, from ``submit`` to the
  start of its flush (sum of the flushes' ``queued_ms_sum`` over their
  ``requests``);
- ``engine_host_ms``: mean host time of a flush, its span less its
  ``engine.fetch`` children (the waits for the device's scores);
- ``engine_idle_share``: device idle time inside ``engine.flush`` spans and
  their children over the window, %;
- ``serve_history_fill``: real history events over the history slots the
  scored batches padded them to (``history_events`` / ``history_slots``), %.

A trace of a program without these spans gives no numbers (``engine``
returns an empty dict).

    python3 chipbench/program_spans.py <trace.xplane.pb>

prints the reduction of one saved trace as JSON.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import os
import sys
from typing import Any, Dict, List, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import trace  # noqa: E402

PREFIX = "repro."
FLUSH = "repro.engine.flush"
FETCH = "repro.engine.fetch"
ENGINE = "repro.engine."
OUTSIDE = "outside any span"


@dataclasses.dataclass
class Span:
    name: str
    start: float                 # ns, the device trace's clock
    end: float                   # ns
    thread: str                  # the host line it was recorded on
    args: Dict[str, Any]


@dataclasses.dataclass
class Reduction:
    window_s: float
    spans: List[Span]            # program spans inside the window
    idle_in: Dict[str, float]    # innermost open span -> idle seconds


def load_spans(path: str) -> List[Span]:
    """The ``repro.*`` host events of one ``.xplane.pb``, with their args."""
    from jax.profiler import ProfileData
    return [Span(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name,
                 dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == trace.HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def _owners(spans: List[Span]) -> Tuple[List[float], List[str]]:
    """Cuts the time line at every span edge: ``names[i]`` is the innermost
    span open over ``[bounds[i], bounds[i + 1])`` (the shortest that holds
    it), or ``OUTSIDE``."""
    bounds = sorted({t for s in spans for t in (s.start, s.end)})
    names = []
    for a, b in zip(bounds, bounds[1:]):
        mid = (a + b) / 2
        holding = [s for s in spans if s.start <= mid < s.end]
        names.append(min(holding, key=lambda s: s.end - s.start).name
                     if holding else OUTSIDE)
    return bounds, names


def _idle(raw: trace.Raw, lo: float, hi: float) -> List[trace.Interval]:
    """Device idle intervals in [lo, hi], over every chip in use."""
    out = []
    for ops in raw.devices.values():
        merged = trace.merge(trace.clip([(o.start, o.end) for o in ops],
                                        lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        out += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
    return out


def reduce(raw: trace.Raw, spans: List[Span]) -> Reduction:
    windows = [(s, e) for n, s, e in raw.spans if n == trace.WINDOW_SPAN]
    if not windows or not raw.devices:
        raise ValueError(f"trace holds no {trace.WINDOW_SPAN} span "
                         f"({len(windows)}) or no device ({len(raw.devices)})")
    lo, hi = windows[-1]
    inside = [s for s in spans if s.end > lo and s.start < hi]
    bounds, names = _owners(inside)
    idle_in: Dict[str, float] = {}
    for s, e in _idle(raw, lo, hi):
        cuts = [s] + bounds[bisect.bisect_right(bounds, s):
                            bisect.bisect_left(bounds, e)] + [e]
        for a, b in zip(cuts, cuts[1:]):
            i = bisect.bisect_right(bounds, (a + b) / 2) - 1
            name = names[i] if 0 <= i < len(names) else OUTSIDE
            idle_in[name] = idle_in.get(name, 0.0) + (b - a) / 1e9
    n = len(raw.devices)
    return Reduction(window_s=(hi - lo) / 1e9, spans=inside,
                     idle_in={k: v / n for k, v in idle_in.items()})


def engine(red: Reduction) -> Dict[str, float]:
    """The engine's numbers (module docstring); those the trace holds
    nothing for are left out."""
    flushes = [s for s in red.spans if s.name == FLUSH]
    if not flushes:
        return {}
    out = {}
    online = [s.args for s in flushes if "queued_ms_sum" in s.args]
    if online:
        out["engine_queue_ms"] = (sum(a["queued_ms_sum"] for a in online)
                                  / sum(a["requests"] for a in online))
    fetches = [s for s in red.spans if s.name == FETCH]
    host_ns = 0.0
    for f in flushes:
        waited = sum(x.end - x.start for x in fetches if x.thread == f.thread
                     and f.start <= x.start and x.end <= f.end)
        host_ns += f.end - f.start - waited
    out["engine_host_ms"] = host_ns / len(flushes) / 1e6
    idle = sum(v for k, v in red.idle_in.items() if k.startswith(ENGINE))
    out["engine_idle_share"] = 100.0 * idle / red.window_s
    slots = sum(s.args.get("history_slots", 0) for s in flushes)
    if slots:
        out["serve_history_fill"] = 100.0 * sum(
            s.args["history_events"] for s in flushes) / slots
    return out


def summary(path: str) -> Dict[str, Any]:
    """What the command line prints for one saved trace."""
    red = reduce(trace.load_file(path), load_spans(path))
    per_name: Dict[str, List[float]] = {}
    for s in red.spans:
        per_name.setdefault(s.name, []).append((s.end - s.start) / 1e9)
    return {"window_s": red.window_s,
            "idle_in_s": dict(sorted(red.idle_in.items(), key=lambda kv: -kv[1])),
            "spans": {k: {"count": len(v), "total_s": sum(v)}
                      for k, v in sorted(per_name.items())},
            "engine": engine(red)}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit("usage: python3 chipbench/program_spans.py <trace.xplane.pb>")
    print(json.dumps(summary(sys.argv[1]), indent=1))
