"""The program's own spans in a benchmark trace.

The program marks its layers with ``livestack.*`` TraceAnnotations
(``repro/obs.py``), and its compile counter puts a ``livestack.compile``
event inside the span that built an executable.  They lie on the
profiler's host planes, on the same clock as the device planes.
``tracereduce.Trace`` keeps only the benchmark's ``bench.*`` host
events, so this module reads the program's events from the same profile
and measures them against that ``Trace``'s window and busy time: their
lengths, counts, the device-idle time inside each, and the idle time
cut at every program span's boundary and named by the innermost one.

A reader gets them with ``of(ctx)``: ``None`` where the profile holds
no program event (a program without ``repro.obs``), else a
``ProgramSpans``, kept on ``ctx`` for the cell's other readers.
"""
from __future__ import annotations

import bisect
import collections
import glob
import pathlib
import sys
from typing import Dict, List, Optional, Sequence, Tuple

PROGRAM = "livestack."
COMPILE = PROGRAM + "compile"

Event = Tuple[float, float, str]


def events(pdata) -> List[Event]:
    """The program's events on the host planes of a profile."""
    return [(e.start_ns, e.end_ns, e.name)
            for plane in pdata.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM)]


class ProgramSpans:
    """Program events of one trace, read against ``trace`` (a
    ``tracereduce.Trace``: its window and its chips' busy time)."""

    def __init__(self, trace, program: Sequence[Event]):
        self.trace = trace
        self.program = list(program)

    def _in_window(self, name: str) -> List[Tuple[float, float]]:
        lo, hi = self.trace.lo, self.trace.hi
        return [(max(s, lo), min(e, hi)) for s, e, n in self.program
                if n == name and e > lo and s < hi]

    def span_ns(self, name: str) -> float:
        """Total length of the program spans called ``name`` (full
        name, ``livestack.<span>``) inside the window."""
        return sum(e - s for s, e in self._in_window(name))

    def count(self, name: str) -> int:
        """Number of program events called ``name`` in the window."""
        return len(self._in_window(name))

    def idle_in(self, name: str) -> List[float]:
        """Per program span called ``name``, in order, the ns inside it
        (and the window) in which no op ran, averaged over the chips."""
        chips = sorted(self.trace.ops) or [0]
        busy = [self.trace.busy_intervals(c) for c in chips]
        starts = [[s for s, _ in b] for b in busy]
        return [sum((e - s) - _overlap(b, st, s, e)
                    for b, st in zip(busy, starts)) / len(chips)
                for s, e in self._in_window(name)]

    def idle_gaps(self, k: int) -> List[List]:
        """The window's idle time cut at every program span's boundary,
        each piece named by the innermost program span open over it
        (``bench.window`` where none is), summed by name, seconds
        averaged over the chips."""
        tr = self.trace
        agg: Dict[str, float] = collections.Counter()
        chips = sorted(tr.ops) or [0]
        segs = self._segments()
        for c in chips:
            j, t = 0, tr.lo
            for s, e in tr.busy_intervals(c) + [(tr.hi, tr.hi)]:
                if s > t:
                    while segs[j][1] <= t:
                        j += 1
                    i = j
                    while i < len(segs) and segs[i][0] < s:
                        a, b, n = segs[i]
                        agg[n] += (min(b, s) - max(a, t)) * 1e-9 / len(chips)
                        i += 1
                t = max(t, e)
        return [[n, v] for n, v in agg.most_common(k)]

    def _segments(self) -> List[Event]:
        """The window cut at every boundary of a program span (compile
        events aside), each piece named by the innermost span open over
        it: the one opened last, of two opened at once the shorter."""
        lo, hi = self.trace.lo, self.trace.hi
        spans = [(max(s, lo), min(e, hi), n) for s, e, n in self.program
                 if n != COMPILE and e > lo and s < hi and e > s]
        opens: Dict[float, list] = collections.defaultdict(list)
        closes: Dict[float, list] = collections.defaultdict(list)
        for i, (s, e, _) in enumerate(spans):
            opens[s].append(i)
            closes[e].append(i)
        edges = sorted({lo, hi, *opens, *closes})
        stack: List[int] = []
        out = []
        for a, b in zip(edges, edges[1:]):
            for i in closes.get(a, ()):
                stack.remove(i)
            stack.extend(sorted(opens.get(a, ()),
                                key=lambda i: spans[i][0] - spans[i][1]))
            out.append((a, b, spans[stack[-1]][2] if stack
                        else "bench.window"))
        return out


def of(ctx) -> Optional[ProgramSpans]:
    """The program's spans of the trace a reader was handed, or None
    where it holds none.  The harness hands readers the reduced trace
    only; the profile it came from is found through the ``trace_dir``
    of the frame that loaded it (``harness.measure``)."""
    if not hasattr(ctx, "program"):
        ctx.program = None
        log_dir = _trace_dir(ctx.trace)
        if log_dir is not None:
            files = sorted(glob.glob(str(pathlib.Path(log_dir) / "plugins"
                                         / "profile" / "*" / "*.xplane.pb")))
            if files:
                from jax.profiler import ProfileData
                program = events(ProfileData.from_file(files[-1]))
                ctx.program = ProgramSpans(ctx.trace, program) \
                    if program else None
    return ctx.program


def _trace_dir(trace):
    """``trace_dir`` of the nearest calling frame that holds ``trace``
    as ``tr`` (``harness.measure``'s names), else None."""
    f = sys._getframe(1)
    while f is not None:
        loc = f.f_locals
        if loc.get("tr") is trace and "trace_dir" in loc:
            return loc["trace_dir"]
        f = f.f_back
    return None


def _overlap(intervals: Sequence[Tuple[float, float]],
             starts: Sequence[float], s: float, e: float) -> float:
    """Length of ``(s, e)`` covered by sorted disjoint ``intervals``
    (``starts``: their starts)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(intervals) and intervals[i][0] < e:
        total += max(0.0, min(intervals[i][1], e) - max(intervals[i][0], s))
        i += 1
    return total
