"""Median client-side latency over every request of the window."""
from bench.harness.record import percentile


def read(run):
    return percentile(run.latencies(), 50)
