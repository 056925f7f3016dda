"""The program's own spans in a traced window, and the idle time of the chip
charged to the host work that was open while it idled.

The program writes ``repro.*`` spans (``src/repro/core/spans.py``) into the
same ``jax.profiler`` trace that ``trace.py`` reduces, so they share the
device's clock; ``trace.load_events`` reads them with the rest.  Unlike the
harness's ``bench.*`` spans they nest: a ``repro.dispatch`` lies inside a
``repro.group`` inside a ``repro.pump``.  For the window (``bench.window``)
this module gives, per span name:

* ``count``: the spans that overlap the window;
* ``total_s``: their seconds inside the window;
* ``self_s``: the seconds in which a span of that name was the innermost open
  program span, which is its duration less what its child spans cover;
* ``idle_s``: the seconds in which no operation ran on the chip while a span
  of that name was the innermost open one;

and the idle seconds with no program span open (the program was not running:
the harness waited for an arrival, for admission's window or for outputs).
A traced run hands the result to the metric readers as ``Window.spans`` and
prints ``table`` on standard error.

Run as a script it is ``bench/run.py --trace 1`` with the same arguments::

  python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

if __name__ == "__main__":
    import os
    import runpy
    import sys

    sys.argv += ["--trace", "1"]
    runpy.run_path(os.path.join(os.path.dirname(os.path.abspath(__file__)), "run.py"),
                   run_name="__main__")

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

from bench import trace as tracing

PREFIX = tracing.PROGRAM_SPAN_PREFIX


@dataclasses.dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    idle_s: float = 0.0


@dataclasses.dataclass
class Summary:
    window_s: float
    chips: int                    # chips that ran an operation in the window
    idle_s: float                 # device idle, averaged over those chips
    unspanned_idle_s: float       # of it, with no program span open
    spans: Dict[str, SpanStats]   # by span name, without the prefix

    @property
    def host_idle_s(self) -> float:
        """Device idle time while a program span was open."""
        return sum(s.idle_s for s in self.spans.values())


def innermost(spans: Sequence) -> List[Tuple[float, float, object]]:
    """``(start, end, span)`` pieces covering every instant at which a span is
    open, each naming the innermost one (the latest started) open then."""
    pieces: List[Tuple[float, float, object]] = []
    stack: List = []
    t = -math.inf

    def run_to(x: float) -> None:
        nonlocal t
        while stack and stack[-1].end_ns <= x:
            top = stack.pop()
            if top.end_ns > t:
                pieces.append((t, top.end_ns, top))
                t = top.end_ns
        if stack and x > t:
            pieces.append((t, x, stack[-1]))
        t = max(t, x)

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        run_to(s.start_ns)
        stack.append(s)
    run_to(math.inf)
    return pieces


def _overlap_total(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(events: Sequence) -> Summary:
    windows = [e for e in events if e.name == tracing.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {tracing.WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = [e for e in events if e.name.startswith(PREFIX)
             and e.end_ns > lo and e.start_ns < hi]
    stats: Dict[str, SpanStats] = {}
    for s in spans:
        st = stats.setdefault(s.name[len(PREFIX):], SpanStats())
        st.count += 1
        st.total_s += (min(s.end_ns, hi) - max(s.start_ns, lo)) * 1e-9
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for a, b, s in innermost(spans):
        for piece in tracing.clip([(a, b)], lo, hi):
            by_name.setdefault(s.name[len(PREFIX):], []).append(piece)
    for name, pieces in by_name.items():
        stats[name].self_s = sum(b - a for a, b in pieces) * 1e-9

    ops: Dict[str, List] = {}
    for e in events:
        if e.plane.startswith(tracing.DEVICE_PLANE_PREFIX) and e.line == tracing.OPS_LINE:
            ops.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    chips, idle_ns = 0, 0.0
    idle_by_name: Dict[str, float] = {}
    for plane in sorted(ops):
        busy = tracing.clip(tracing.union(ops[plane]), lo, hi)
        if not busy:
            continue
        chips += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle_ns += sum(b - a for a, b in idle)
        for name, pieces in by_name.items():
            idle_by_name[name] = idle_by_name.get(name, 0.0) + _overlap_total(pieces, idle)
    for name, ns in idle_by_name.items():
        stats[name].idle_s = ns / chips * 1e-9
    idle_s = idle_ns / chips * 1e-9 if chips else 0.0
    spanned = sum(s.idle_s for s in stats.values())
    return Summary(window_s=(hi - lo) * 1e-9, chips=chips, idle_s=idle_s,
                   unspanned_idle_s=idle_s - spanned, spans=stats)


def table(summary: Summary) -> str:
    """Device idle seconds by innermost program span, most first, with each
    span's count and self seconds."""
    rows = sorted(summary.spans.items(), key=lambda kv: -kv[1].idle_s)
    lines = [f"idle by innermost program span: {summary.idle_s:.6f} s idle of "
             f"{summary.window_s:.6f} s on {summary.chips} chip(s)",
             f"  {'span':<10} {'idle_s':>10} {'self_s':>10} {'count':>8}",
             f"  {'none':<10} {summary.unspanned_idle_s:>10.6f}"]
    lines += [f"  {name:<10} {s.idle_s:>10.6f} {s.self_s:>10.6f} {s.count:>8d}"
              for name, s in rows]
    return "\n".join(lines)
