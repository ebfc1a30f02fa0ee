"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The window is the benchmark's own host span ``bench.window``.  On each
device plane (``/device:TPU:<i>``) the ``XLA Modules`` line holds one
event per program run: busy time is the length of their union inside
the window, averaged over the devices, and an idle gap is a stretch of
the window with none running, named by the innermost ``bench.*`` span
the host had open at its middle.  The ``XLA Ops`` line holds the ops
inside the programs, named by their HLO instruction (``%fused_pd_step.7
= ...`` is ``fused_pd_step``); control flow (``while``, ``conditional``,
``call``) holds other ops and is left out of the per-op times.  Host and
device events are on one clock in the trace, so a host span's device
time is the union clipped to it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import itertools
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_MODULE_LINE = "XLA Modules"
_OP_LINE = "XLA Ops"
_CONTAINERS = {"while", "conditional", "call"}


def op_name(hlo: str) -> str:
    """``%fused_pd_step.7 = (f32[...]) custom-call(...)`` -> the
    instruction's name without its number: ``fused_pd_step``."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))


def _union(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    """Device ops and host spans of one traced window (nanoseconds on
    the trace's clock, seconds in every number returned)."""

    window: tuple[int, int]
    devices: int
    busy: list                          # union of op intervals, device 0
    busy_s: float                       # averaged over the devices
    op_s: dict                          # op name -> seconds, all devices
    spans: list                         # (name, start, end) bench spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, name: str) -> float:
        """Summed device time of the ops named ``name`` (see
        :func:`op_name`), over all devices."""
        return self.op_s.get(name, 0.0)

    def _busy_until(self, t: float) -> float:
        """Nanoseconds device 0 was busy before ``t``."""
        if not hasattr(self, "_starts"):
            self._starts = [s for s, _ in self.busy]
            self._before = list(itertools.accumulate(
                (e - s for s, e in self.busy), initial=0))
        i = bisect.bisect_right(self._starts, t)
        if i == 0:
            return 0.0
        s, e = self.busy[i - 1]
        return self._before[i - 1] + min(t, e) - s

    def busy_in(self, start: float, end: float) -> float:
        """Seconds device 0 was busy inside [start, end)."""
        return (self._busy_until(end) - self._busy_until(start)) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        return sorted(([n, s] for n, s in self.op_s.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle seconds inside the window summed by the innermost bench
        span open at each gap's middle, the largest ``k``."""
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in self.busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        # spans of one thread nest, so a stack swept in time order holds
        # the open ones, innermost on top
        spans = sorted((s, -e, n) for n, s, e in self.spans
                       if n != WINDOW_SPAN)
        by = collections.Counter()
        stack, i = [], 0
        for s, e in gaps:
            mid = (s + e) // 2
            while i < len(spans) and spans[i][0] <= mid:
                stack.append((-spans[i][1], spans[i][2]))
                i += 1
            stack = [x for x in stack if x[0] > mid]
            name = stack[-1][1] if stack else "outside bench spans"
            by[name] += (e - s) * 1e-9
        return [[n, s] for n, s in by.most_common(k)]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def reduce(path: str) -> TraceSummary:
    """Read the trace at ``path`` and reduce it to its window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, modules, ops = [], [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            runs = []
            for line in plane.lines:
                events = [(ev.name, int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns))
                          for ev in line.events]
                if line.name == _MODULE_LINE:
                    runs.extend((s, e) for _, s, e in events)
                elif line.name == _OP_LINE:
                    ops.extend(events)
            modules.append(runs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s,
                                      s + int(ev.duration_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    if not modules:
        raise ValueError(f"{path}: no device plane")
    unions = [_union(_clip(runs, lo, hi)) for runs in modules]
    op_s = collections.Counter()
    for hlo, s, e in ops:
        name = op_name(hlo)
        if e > lo and s < hi and name not in _CONTAINERS:
            op_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
    busy_s = sum(sum(e - s for s, e in u) for u in unions) * 1e-9 \
        / len(unions)
    return TraceSummary(window=(lo, hi), devices=len(unions),
                        busy=unions[0], busy_s=busy_s, op_s=dict(op_s),
                        spans=spans)
