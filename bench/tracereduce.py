"""Reduction of a JAX profiler trace to the benchmark's numbers.

The TPU trace (``<dir>/plugins/profile/<time>/*.xplane.pb``, read with
``jax.profiler.ProfileData``) has one plane per chip, ``/device:TPU:<i>``,
whose ``XLA Ops`` line holds every operation the chip ran and whose
``XLA Modules`` line holds every program (``jit_<name>(<hash>)``); the
ops of a loop body lie inside the loop's own event.  Host planes hold
the benchmark's spans (``bench.*`` TraceAnnotations) on the same clock.

* busy: the union of the chip's op intervals inside the window span
  ``bench.window``, averaged over the chips used;
* top ops: leaf ops (no other op of the line inside them) summed by
  their HLO name;
* idle gaps: the window minus the busy union, each gap named by the
  innermost ``bench.*`` host span around its midpoint, summed by name.
"""
from __future__ import annotations

import collections
import glob
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def short_name(hlo: str) -> str:
    """``%fusion.78 = s32[...] fusion(...)`` -> ``fusion.78``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def leaves(events: Sequence[Tuple[float, float, str]]):
    """Events with no other event of the line inside them."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, n) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[0] < e and nxt[1] <= e:
            continue            # encloses the next event: a parent
        out.append((s, e, n))
    return out


class Trace:
    """Events of one trace, in ns on the profiler's clock."""

    def __init__(self, ops: Dict[int, List[Tuple[float, float, str]]],
                 modules: Dict[int, List[Tuple[float, float, str]]],
                 spans: List[Tuple[float, float, str]]):
        self.ops = ops            # chip -> [(start, end, hlo text)]
        self.modules = modules    # chip -> [(start, end, module name)]
        self.spans = spans        # host bench.* spans
        windows = [(s, e) for s, e, n in spans if n == "bench.window"]
        if not windows:
            raise ValueError("trace has no bench.window span")
        self.lo, self.hi = windows[0]

    @classmethod
    def from_profile(cls, pdata, n_devices: Optional[int] = None):
        ops, modules, spans = {}, {}, []
        for plane in pdata.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                chip = int(m.group(1))
                if n_devices is not None and chip >= n_devices:
                    continue
                for line in plane.lines:
                    evs = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        ops[chip] = evs
                    elif line.name == "XLA Modules":
                        modules[chip] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend((e.start_ns, e.end_ns, e.name)
                                 for e in line.events
                                 if e.name.startswith("bench."))
        return cls(ops, modules, spans)

    @classmethod
    def load(cls, log_dir, n_devices: Optional[int] = None):
        from jax.profiler import ProfileData
        files = sorted(glob.glob(str(pathlib.Path(log_dir) / "plugins"
                                     / "profile" / "*" / "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no xplane.pb under {log_dir}")
        return cls.from_profile(ProfileData.from_file(files[-1]),
                                n_devices)

    # -- window and busy time ------------------------------------------------
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_intervals(self, chip: int) -> List[Interval]:
        return union(clip([(s, e) for s, e, _ in self.ops.get(chip, [])],
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds some op ran, averaged over the chips in the trace."""
        chips = sorted(self.ops) or [0]
        total = sum(e - s for c in chips
                    for s, e in self.busy_intervals(c))
        return total / len(chips) * 1e-9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    # -- breakdowns ----------------------------------------------------------
    def top_ops(self, k: int) -> List[List]:
        agg: Dict[str, float] = collections.Counter()
        chips = sorted(self.ops)
        for c in chips:
            for s, e, n in leaves(clip_events(self.ops[c], self.lo,
                                              self.hi)):
                agg[short_name(n)] += (e - s) * 1e-9 / len(chips)
        return [[n, v] for n, v in agg.most_common(k)]

    def idle_gaps(self, k: int) -> List[List]:
        agg: Dict[str, float] = collections.Counter()
        chips = sorted(self.ops) or [0]
        inner = [sp for sp in self.spans if sp[2] != "bench.window"]
        for c in chips:
            t = self.lo
            for s, e in self.busy_intervals(c) + [(self.hi, self.hi)]:
                if s > t:
                    agg[self.host_activity((t + s) / 2, inner)] += \
                        (s - t) * 1e-9 / len(chips)
                t = max(t, e)
        return [[n, v] for n, v in agg.most_common(k)]

    @staticmethod
    def host_activity(t: float, spans) -> str:
        best = None
        for s, e, n in spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "bench.window"

    # -- selections for the metric readers -----------------------------------
    def span_ns(self, name: str) -> float:
        """Total length of the host spans called ``name``."""
        return sum(min(e, self.hi) - max(s, self.lo)
                   for s, e, n in self.spans
                   if n == name and e > self.lo and s < self.hi)

    def module_ns(self, prefix: str) -> float:
        """Device time of programs whose name starts with ``prefix``,
        averaged over the chips."""
        chips = sorted(self.modules) or [0]
        return sum(e - s for c in chips
                   for s, e, n in clip_events(self.modules.get(c, []),
                                              self.lo, self.hi)
                   if n.startswith(prefix)) / len(chips)

    def op_events(self, pred, *, module: Optional[str] = None,
                  span: Optional[str] = None
                  ) -> List[Tuple[float, float, str]]:
        """Leaf ops inside the window whose HLO text satisfies ``pred``,
        over all chips; with ``module`` only those inside a program
        whose name starts with it, with ``span`` only those inside a
        host span of that name (each judged by its midpoint)."""
        host = [(s, e) for s, e, n in self.spans if n == span]
        out = []
        for c in sorted(self.ops):
            mods = [(s, e) for s, e, n in self.modules.get(c, [])
                    if module is not None and n.startswith(module)]
            for ev in leaves(clip_events(self.ops[c], self.lo, self.hi)):
                mid = (ev[0] + ev[1]) / 2
                if not pred(ev[2]):
                    continue
                if module is not None and not _inside(mid, mods):
                    continue
                if span is not None and not _inside(mid, host):
                    continue
                out.append(ev)
        return out


def _inside(t: float, intervals: Sequence[Interval]) -> bool:
    return any(s <= t <= e for s, e in intervals)


def clip_events(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]
