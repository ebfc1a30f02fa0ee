"""repro.obs: unified telemetry for the solver, serving, and federated
layers.

Public surface::

    from repro import obs

    obs.enable()                        # or REPRO_OBS=1 in the env
    obs.events.attach("events.jsonl")   # stream one event per request

    with obs.span("my_phase"):          # repro.my_phase in a profile
        ...
    obs.counter("my_total").inc()
    count, seconds = obs.compiles()     # JAX compiles so far

    print(obs.export.prometheus_text())  # or obs.export.export_json()

The registry defaults **off**; spans are the profiler's own
annotations, which do nothing unless a profile is being recorded: see
``telemetry.py`` for the zero-overhead contract, ``events.py`` for the
request event log, ``export.py`` for JSON/Prometheus snapshots, and
``profile.py`` for device-profile phase annotation.
"""
from repro.obs import events, export, profile, telemetry
from repro.obs.telemetry import (COUNT_BUCKETS, REGISTRY,
                                 SECONDS_BUCKETS, SPAN_PREFIX, Counter,
                                 Gauge, Histogram, SpanRecord, compiles,
                                 counter, device_fetch, disable, enable,
                                 enabled, gauge, histogram,
                                 profiled_spans, span)


def reset() -> None:
    """Clear all metrics, the profiled spans and the event log (test
    isolation)."""
    telemetry.reset()
    events.reset()


__all__ = [
    "COUNT_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "REGISTRY",
    "SECONDS_BUCKETS",
    "SPAN_PREFIX",
    "SpanRecord",
    "compiles",
    "counter",
    "device_fetch",
    "disable",
    "enable",
    "enabled",
    "events",
    "export",
    "gauge",
    "histogram",
    "profile",
    "profiled_spans",
    "reset",
    "span",
    "telemetry",
]
