"""Seconds from the start of the run to the start of the measured
window: imports, the deployment built from the seed, the program's
planning, compiles or loads from the cache, and the warm-up."""


def read(run):
    return run.setup_s
