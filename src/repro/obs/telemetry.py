"""Process-wide metrics registry: counters, gauges, histograms, spans,
and the compile tally.

The single place every layer meters into — the solver's transfer
counter, the plan cache's compile accounting, the serving request
stream, the federated CommLedger totals.  Everything is host-side and
synchronous (the request loop is), and the registry is **off by
default**: it only records when observability is enabled, via the
``REPRO_OBS=1`` environment variable or :func:`enable`.

Spans are profiler spans: :func:`span` always enters
``jax.profiler.TraceAnnotation("repro.<name>")``, so every profile of
the program (``obs.profile.trace`` or any ``jax.profiler`` session)
shows its phases on the device's clock, nested by thread.  While a
profile is being recorded, each span is also kept in
:func:`profiled_spans` with its host-clock ends and the compiles that
finished inside it.

Zero-overhead contract: with observability disabled and no profile
being recorded, every mutation method returns after a single attribute
check, :func:`span` returns the profiler's own annotation (which does
nothing without a profile: no registry lookup, no clock read), and
:func:`device_fetch` degrades to a bare ``jax.device_get``.  The compile
listener runs only when JAX compiles.  Nothing here ever runs *inside*
jitted code — device-side phase annotation is ``jax.named_scope``
(:mod:`repro.obs.profile`), which costs only at trace time — so enabling
telemetry cannot change what XLA executes.

Metric handles are process-wide singletons keyed by (name, labels):
``counter("x", tenant="a")`` returns the same object on every call, so
call sites never hold state.  Histograms use fixed buckets (Prometheus
style: cumulative counts at export), which keeps p50/p99 derivable at
any time without storing samples.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import os
import threading
import time

import jax

#: Latency buckets (seconds): ~log-spaced from 100us to 30s.  Chosen to
#: straddle the repo's real request latencies — smoke solves run ~1ms-1s,
#: cold compiles seconds.
SECONDS_BUCKETS = (1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2,
                   5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

#: Small-count buckets: batch widths, queue waits (in submit ticks).
COUNT_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


def _env_enabled() -> bool:
    val = os.environ.get("REPRO_OBS", "").strip().lower()
    return val not in ("", "0", "false", "no", "off")


class _State:
    __slots__ = ("enabled",)

    def __init__(self):
        self.enabled = _env_enabled()


_STATE = _State()


def enabled() -> bool:
    """True when the registry records (``REPRO_OBS=1`` or enable())."""
    return _STATE.enabled


def enable() -> None:
    _STATE.enabled = True


def disable() -> None:
    _STATE.enabled = False


# ---------------------------------------------------------------------------
# Metric types
# ---------------------------------------------------------------------------

class Counter:
    """Monotonic counter; ``inc`` is a no-op while disabled."""

    kind = "counter"
    __slots__ = ("name", "labels", "help", "value")

    def __init__(self, name: str, labels: tuple, help: str = ""):
        self.name = name
        self.labels = labels
        self.help = help
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if _STATE.enabled:
            self.value += n


class Gauge:
    """Last-write-wins value; ``set`` is a no-op while disabled."""

    kind = "gauge"
    __slots__ = ("name", "labels", "help", "value")

    def __init__(self, name: str, labels: tuple, help: str = ""):
        self.name = name
        self.labels = labels
        self.help = help
        self.value = 0.0

    def set(self, v: float) -> None:
        if _STATE.enabled:
            self.value = float(v)


class Histogram:
    """Fixed-bucket histogram (upper bounds; +Inf bucket implicit).

    ``counts[i]`` holds observations <= ``bounds[i]`` (non-cumulative in
    memory; the Prometheus exporter accumulates).  ``percentile`` reads
    a quantile back out by linear interpolation inside the bucket the
    quantile lands in — exact enough for rolling p50/p99 without keeping
    samples.
    """

    kind = "histogram"
    __slots__ = ("name", "labels", "help", "bounds", "counts", "sum",
                 "count")

    def __init__(self, name: str, labels: tuple, help: str = "",
                 buckets: tuple = SECONDS_BUCKETS):
        self.name = name
        self.labels = labels
        self.help = help
        self.bounds = tuple(float(b) for b in buckets)
        self.counts = [0] * (len(self.bounds) + 1)   # last = +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        if not _STATE.enabled:
            return
        v = float(v)
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.sum += v
        self.count += 1

    def percentile(self, q: float) -> float:
        """Quantile in [0, 1] from the bucket counts; 0.0 when empty.

        Observations in the +Inf bucket report the largest finite bound
        — a floor, not an estimate, but it keeps the value finite.
        """
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            prev_cum, cum = cum, cum + c
            if cum >= target:
                if i >= len(self.bounds):          # +Inf bucket
                    return self.bounds[-1]
                lo = self.bounds[i - 1] if i else 0.0
                hi = self.bounds[i]
                frac = (target - prev_cum) / c
                return lo + frac * (hi - lo)
        return self.bounds[-1]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

class Registry:
    """Process-wide metric store keyed by (name, sorted labels)."""

    def __init__(self):
        self._metrics: dict = {}
        self._lock = threading.Lock()

    def get(self, cls, name: str, help: str, labels: dict, **kw):
        lab = tuple(sorted((str(k), str(v)) for k, v in labels.items()))
        key = (name, lab)
        m = self._metrics.get(key)
        if m is None:
            with self._lock:
                m = self._metrics.get(key)
                if m is None:
                    m = cls(name, lab, help=help, **kw)
                    self._metrics[key] = m
        if type(m) is not cls:
            raise TypeError(
                f"metric {name!r} already registered as {m.kind}, "
                f"requested {cls.kind}")
        return m

    def metrics(self) -> list:
        """All registered metrics, sorted by (name, labels)."""
        return [m for _, m in sorted(self._metrics.items())]

    def find(self, name: str) -> list:
        return [m for m in self.metrics() if m.name == name]

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()


REGISTRY = Registry()


def counter(name: str, help: str = "", **labels) -> Counter:
    return REGISTRY.get(Counter, name, help, labels)


def gauge(name: str, help: str = "", **labels) -> Gauge:
    return REGISTRY.get(Gauge, name, help, labels)


def histogram(name: str, help: str = "",
              buckets: tuple = SECONDS_BUCKETS, **labels) -> Histogram:
    return REGISTRY.get(Histogram, name, help, labels, buckets=buckets)


def reset() -> None:
    """Clear every registered metric and the profiled spans (test
    isolation; events reset separately via
    :func:`repro.obs.events.reset`).  The compile tally is never
    reset: it counts the process's compiles."""
    REGISTRY.reset()
    _SPAN_LOG.clear()


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

#: Prefix of every span's name in a profile.
SPAN_PREFIX = "repro."

#: Spans kept by :func:`profiled_spans` (the oldest are dropped first).
_SPAN_LOG_SIZE = 65536

_ANNOTATION = jax.profiler.TraceAnnotation


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One span taken while a profile was being recorded.

    ``start``/``end`` are ``time.perf_counter()`` seconds; ``depth`` is
    the number of spans open around it on its thread; ``compiles`` the
    JAX compiles (see :func:`compiles`) that finished while it was open;
    ``labels`` its keyword stats in the profile.
    """

    name: str
    start: float
    end: float
    depth: int
    compiles: int
    labels: dict


_SPAN_LOG: collections.deque = collections.deque(maxlen=_SPAN_LOG_SIZE)
_OPEN = threading.local()


class _Span:
    """A profiler span that also times itself: into the
    ``repro_span_seconds`` histogram while observability is enabled, and
    into :func:`profiled_spans` while a profile is being recorded."""

    __slots__ = ("_name", "_labels", "_ann", "_log", "_t0", "_c0",
                 "_depth")

    def __init__(self, name: str, labels: dict, log: bool):
        self._name = name
        self._labels = labels
        self._log = log
        self._ann = _ANNOTATION(SPAN_PREFIX + name, **labels)

    def __enter__(self):
        self._ann.__enter__()
        if self._log:
            self._depth = getattr(_OPEN, "depth", 0)
            _OPEN.depth = self._depth + 1
            self._c0 = _COMPILES.count
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._log:
            _OPEN.depth = self._depth
            n = _COMPILES.count - self._c0
            self._ann.set_metadata(compiles=n)
            _SPAN_LOG.append(SpanRecord(self._name, self._t0, t1,
                                        self._depth, n, self._labels))
        self._ann.__exit__(*exc)
        if _STATE.enabled:
            histogram("repro_span_seconds",
                      help="host-side span timings by phase",
                      span=self._name).observe(t1 - self._t0)
        return False


def span(name: str, **labels):
    """A span of the program's host path: ``repro.<name>`` in a profile,
    with ``labels`` as its stats, nested in the span open around it.

    With observability disabled and no profile being recorded this is
    ``jax.profiler.TraceAnnotation`` itself, the profiler's no-op: no
    clock read, no registry lookup.  While enabled, the span's duration
    goes to ``repro_span_seconds{span=name}``; while a profile is being
    recorded, the span is kept in :func:`profiled_spans` and its
    compiles are added to its stats.
    """
    log = _ANNOTATION.is_enabled()
    if not (_STATE.enabled or log):
        return _ANNOTATION(SPAN_PREFIX + name, **labels)
    return _Span(name, labels, log)


def profiled_spans() -> list[SpanRecord]:
    """The spans taken while a profile was being recorded, oldest first.

    The same spans as the profile's ``repro.*`` host events, on the
    host clock: for a reader in this process that has the device's
    busy intervals but not the profile's host events.
    """
    return list(_SPAN_LOG)


# ---------------------------------------------------------------------------
# The compile tally
# ---------------------------------------------------------------------------

#: JAX's event for a backend compile or a load from the persistent
#: compilation cache (``jax._src.dispatch``).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Tally:
    __slots__ = ("count", "seconds", "lock")

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.lock = threading.Lock()


_COMPILES = _Tally()


def _on_duration(event: str, seconds: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    with _COMPILES.lock:
        _COMPILES.count += 1
        _COMPILES.seconds += seconds
    if _STATE.enabled:
        counter("repro_compiles_total",
                help="JAX backend compiles and compilation-cache "
                     "loads").inc()
        counter("repro_compile_seconds_total",
                help="seconds spent in JAX backend compiles and "
                     "compilation-cache loads").inc(seconds)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> tuple[int, float]:
    """(count, seconds) of the JAX compiles this process has made: each
    backend compile or load from the persistent compilation cache,
    counted whether or not observability is enabled."""
    return _COMPILES.count, _COMPILES.seconds


# ---------------------------------------------------------------------------
# The library-level device->host transfer counter
# ---------------------------------------------------------------------------

def device_fetch(x):
    """The library's single device->host fetch point.

    Every *deliberate* transfer the solver stack performs (the one
    stopping-iteration fetch of a tol solve, the one per masked sweep,
    the one per batched solve) routes through here, so
    ``repro_transfers_device_to_host_total`` is the production twin of
    the test-only transfer guard: "one transfer per tol solve" is a
    dashboard fact, not just a pytest fact.  Calls ``jax.device_get``
    through the module attribute, so the test guard's monkeypatch still
    counts these fetches too.
    """
    import jax

    if _STATE.enabled:
        counter("repro_transfers_device_to_host_total",
                help="deliberate device->host fetches by the solver "
                     "stack").inc()
    return jax.device_get(x)
