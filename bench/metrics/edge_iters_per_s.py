"""Edges times Algorithm-1 iterations completed by the window's calls,
over the time from the window's start to the last completion."""


def read(run):
    return run.num_edges * run.iterations / run.window_s
