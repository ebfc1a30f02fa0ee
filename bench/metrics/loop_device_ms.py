"""Milliseconds the device was busy inside the program's
``repro.solver_engine`` spans (each tol solve's loop, dispatched and run
through the fetch of its stopping iteration) per Algorithm-1 iteration
of the calls that hold them: the engine loop with the kernel."""
from bench.harness import program_spans


def read(run):
    spans = program_spans.from_program(run)
    if not spans:
        return None
    return program_spans.loop_device_ms(run.trace, spans,
                                        program_spans.calls(run))
