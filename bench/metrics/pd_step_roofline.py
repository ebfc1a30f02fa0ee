"""The fused kernel's share of its roofline: the least time of the
iterations completed in the traced window (bytes over the memory
bandwidth or operations over the peak rate, whichever is larger;
``bench.harness.cost``) over the summed device time of the kernel's
events."""

# the fused primal-dual step's custom call, the only Mosaic kernel on
# the fused route
KERNEL = "fused_pd_step"


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds(KERNEL)
    if kernel_s <= 0 or not run.iterations:
        return None
    return 100.0 * run.iterations * run.cost.seconds(run.peaks()) / kernel_s
