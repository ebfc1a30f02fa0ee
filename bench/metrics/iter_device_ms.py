"""Milliseconds the device was busy in the traced window per Algorithm-1
iteration completed there (the engine loop and its executors, with the
per-call set-up and certificate programs they bring)."""


def read(run):
    if run.trace is None or not run.iterations:
        return None
    return 1e3 * run.trace.busy_s / run.iterations
