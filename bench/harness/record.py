"""What a run leaves for its metrics to read."""
from __future__ import annotations

import dataclasses
import statistics


@dataclasses.dataclass
class Event:
    """One call or request as its client saw it (host clock).

    ``ok`` is False for an error and for a response whose residual
    missed its tol."""

    start: float
    end: float
    iterations: int
    ok: bool

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Run:
    """A finished measured window and what was read around it."""

    config: dict
    traffic: dict
    setup_s: float
    window: tuple[float, float]       # host clock: start, last completion
    events: list[Event]
    num_edges: int
    cost: object                      # bench.harness.cost.IterationCost
    device_kind: str
    trace: object = None              # bench.harness.trace.TraceSummary

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def iterations(self) -> int:
        return sum(e.iterations for e in self.events)

    def latencies(self) -> list[float]:
        return [e.seconds for e in self.events]

    def peaks(self) -> dict:
        """The device's published peaks (an unknown kind raises)."""
        from bench.harness.peaks import peaks
        return peaks(self.device_kind)

    def trace_ns(self, t: float) -> float:
        """A host-clock time inside the window on the trace's clock
        (the window span's two ends pin the map)."""
        lo, hi = self.trace.window
        t0, t1 = self.window
        return lo + (t - t0) / (t1 - t0) * (hi - lo)


def percentile(values, q: float) -> float:
    """The q-th percentile (0 < q < 100), interpolated between order
    statistics (``statistics.quantiles``' inclusive method)."""
    values = sorted(values)
    if len(values) == 1:
        return float(values[0])
    cuts = statistics.quantiles(values, n=100, method="inclusive")
    return float(cuts[int(round(q)) - 1])
