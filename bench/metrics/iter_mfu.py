"""The whole step's share of the chip's peak: the least time of the
iterations completed in the traced window (``bench.harness.cost``;
bytes over the memory bandwidth for this memory-bound step) over the
window's wall time."""


def read(run):
    if run.trace is None or not run.iterations:
        return None
    return (100.0 * run.iterations * run.cost.seconds(run.peaks())
            / run.trace.window_s)
