"""The program's own spans beside a traced window, and the per-layer
numbers read from them.

The program (``repro.obs``) opens a span ``repro.<name>`` at each layer
boundary of its host path.  While a profile is being recorded it also
keeps each span on the host clock, with its depth (the spans open
around it on its thread) and the JAX compiles that finished inside it
(``repro.obs.profiled_spans``).  The trace reduction
(:mod:`bench.harness.trace`) keeps the benchmark's ``bench.*`` spans
only, so :func:`from_program` takes the window's spans from the
program and maps them onto the trace's clock by the window's two ends
(``Run.trace_ns``); there they are clipped against the device's busy
intervals like any host span.  A program that keeps no such spans gives
nothing to read, and every number here is then None.

:func:`from_xplane` reads the same spans from a kept trace, where they
are the host events named ``repro.*`` (a name is cut at ``#``), with
their stats.
"""
from __future__ import annotations

import dataclasses

PREFIX = "repro."
ENGINE = "solver_engine"
REQUEST = "service_solve"
SERVICE = "service_"


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span on the trace's clock (nanoseconds)."""

    name: str           # without the ``repro.`` prefix
    start: float
    end: float
    depth: int
    stats: dict         # its labels, and ``compiles`` where it was kept


def from_program(run) -> list[Span] | None:
    """The spans the program kept inside the run's window, on the
    trace's clock; None for an untraced run or a program that keeps
    none."""
    if run.trace is None:
        return None
    try:
        from repro import obs
        kept = obs.profiled_spans()
    except (ImportError, AttributeError):
        return None
    lo, hi = run.window
    return [Span(k.name, run.trace_ns(k.start), run.trace_ns(k.end),
                 k.depth, {**k.labels, "compiles": k.compiles})
            for k in kept if lo <= k.start and k.end <= hi]


def from_xplane(path: str) -> list[Span]:
    """The ``repro.*`` host events of the trace at ``path``, each with
    its depth among them on its thread."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(
                (int(ev.start_ns), -int(ev.start_ns + ev.duration_ns),
                 ev.name.split("#", 1)[0], ev)
                for ev in line.events if ev.name.startswith(PREFIX))
            open_ends = []
            for s, neg_e, name, ev in events:
                open_ends = [e for e in open_ends if e > s]
                spans.append(Span(name[len(PREFIX):], s, -neg_e,
                                  len(open_ends),
                                  {k: v for k, v in ev.stats}))
                open_ends.append(-neg_e)
    return spans


def calls(run) -> list[tuple[float, float, int]]:
    """The window's calls or requests: (start, end) on the trace's
    clock, and the iterations each ran."""
    return [(run.trace_ns(e.start), run.trace_ns(e.end), e.iterations)
            for e in run.events]


def _busy(trace, spans) -> float:
    """Seconds device 0 was busy inside ``spans`` (disjoint)."""
    return sum(trace.busy_in(s.start, s.end) for s in spans)


def loop_device_ms(trace, spans, calls) -> float | None:
    """Device milliseconds inside the engine spans per iteration of the
    calls that hold them."""
    engine = [s for s in spans if s.name == ENGINE]
    its = sum(i for lo, hi, i in calls
              if any(lo <= s.start and s.end <= hi for s in engine))
    if not engine or not its:
        return None
    return 1e3 * _busy(trace, engine) / its


def call_device_ms(trace, spans, calls) -> float | None:
    """Device milliseconds of the window outside the engine spans, per
    call."""
    engine = [s for s in spans if s.name == ENGINE]
    if not engine or not calls:
        return None
    outside = trace.busy_in(*trace.window) - _busy(trace, engine)
    return 1e3 * outside / len(calls)


def solver_host_ms(trace, spans, requests: int) -> float | None:
    """Milliseconds per request inside the top-level service spans in
    which the device ran nothing."""
    top = [s for s in spans if s.depth == 0 and s.name.startswith(SERVICE)]
    if not top or not requests:
        return None
    idle = sum((s.end - s.start) * 1e-9 - trace.busy_in(s.start, s.end)
               for s in top)
    return 1e3 * idle / requests


def request_compiles(spans) -> float | None:
    """Mean JAX compiles inside a served solve request."""
    counts = [s.stats.get("compiles") for s in spans if s.name == REQUEST]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)


def idle_gaps(trace, spans, k: int = 10) -> list:
    """The window's idle seconds by the innermost span, ``bench.*`` or
    ``repro.*``, open at each gap's middle (``TraceSummary.idle_gaps``
    over both kinds)."""
    both = list(trace.spans) + [(PREFIX + s.name, s.start, s.end)
                                for s in spans]
    return dataclasses.replace(trace, spans=both).idle_gaps(k)
