"""Reduction of a profiler trace to the device metrics of one window.

The harness traces its measured window with ``jax.profiler`` and wraps it in
a host span named ``bench.window``, and its own work in ``bench.*`` spans
(``submit``, ``step``, ``wait_arrival``, ``wait_admission``,
``wait_outputs``).  From the ``.xplane.pb`` file this module reads:

* busy time: the union of the intervals in which an XLA operation ran on a
  chip (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), clipped to the
  window and averaged over the chips that ran anything;
* the device seconds of every operation, named ``<program>:<op>`` by the
  XLA program (line ``XLA Modules``) and the HLO instruction, summed over
  the chips; an operation nested inside another (the body of a loop) counts
  in its parent.  The ten with most time are the breakdown's;
* the idle gaps between operations, each named by the harness span that
  covers most of it (``none`` when no span does).

The program's own spans (``repro.*``) are loaded from the same file and
reduced by ``program_spans.py``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_SPAN_PREFIX = "repro."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load_events(xplane_path: str) -> List[Event]:
    """The device programs and operations, the harness's host spans and the
    program's spans of a trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    events = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith((SPAN_PREFIX, PROGRAM_SPAN_PREFIX)):
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns), float(e.duration_ns)))
    return events


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def op_name(op: Event, modules: Sequence[Event], starts: Sequence[float]) -> str:
    """``<program>:<instruction>``: the XLA module running at the op's start
    (its fingerprint dropped) and the HLO instruction's name."""
    i = bisect.bisect_right(starts, op.start_ns) - 1
    module = modules[i].name.split("(")[0] if i >= 0 and modules[i].end_ns > op.start_ns else "?"
    return f"{module}:{op.name.split(' = ')[0]}"


def top_level(ops: Sequence[Event]) -> List[Event]:
    """The ops not nested inside an earlier op of the same line."""
    out: List[Event] = []
    for e in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        if not out or e.start_ns >= out[-1].end_ns:
            out.append(e)
    return out


def overlap(a: Tuple[float, float], b: Tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


class SpanIndex:
    """The harness's host spans, which never overlap one another, sorted so
    that the spans covering an interval are found by bisection."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted(spans, key=lambda s: s.start_ns)
        self.starts = [s.start_ns for s in self.spans]

    def label(self, a: float, b: float) -> str:
        """The name of the span that covers most of ``[a, b)``."""
        best, best_cover = "none", 0.0
        j = max(bisect.bisect_right(self.starts, a) - 1, 0)
        while j < len(self.spans) and self.starts[j] < b:
            s = self.spans[j]
            cover = overlap((a, b), (s.start_ns, s.end_ns))
            if cover > best_cover:
                best, best_cover = s.name[len(SPAN_PREFIX):], cover
            j += 1
        return best


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # averaged over the chips that ran
    chips: int
    device_ops: List[Tuple[str, float]]  # (XLA op name, seconds), longest first
    idle_gaps: List[Tuple[str, float]]   # (host span, seconds), longest first
    op_seconds: Dict[str, float]         # every XLA op name: seconds


def reduce(events: Sequence[Event], top: int = TOP) -> Summary:
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = SpanIndex([e for e in events
                       if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN])
    by_plane: Dict[str, List[Event]] = {}
    for e in events:
        if e.plane.startswith(DEVICE_PLANE_PREFIX):
            by_plane.setdefault(e.plane, []).append(e)

    op_time: Dict[str, float] = {}
    busy_total, chips = 0.0, 0
    gaps: List[Tuple[str, float]] = []
    for plane in sorted(by_plane):
        ops = [e for e in by_plane[plane] if e.line == OPS_LINE]
        modules = sorted((e for e in by_plane[plane] if e.line == MODULES_LINE),
                         key=lambda e: e.start_ns)
        starts = [m.start_ns for m in modules]
        busy = clip(union((e.start_ns, e.end_ns) for e in ops), lo, hi)
        if not busy:
            continue
        chips += 1
        busy_total += sum(b - a for a, b in busy)
        for e in top_level(ops):
            for a, b in clip([(e.start_ns, e.end_ns)], lo, hi):
                name = op_name(e, modules, starts)
                op_time[name] = op_time.get(name, 0.0) + (b - a)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((spans.label(a, b), (b - a) * 1e-9))
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=(busy_total / chips) * 1e-9 if chips else 0.0,
        chips=chips,
        device_ops=[(name, ns * 1e-9) for name, ns in ops_sorted],
        idle_gaps=gaps[:top],
        op_seconds={name: ns * 1e-9 for name, ns in op_time.items()},
    )
