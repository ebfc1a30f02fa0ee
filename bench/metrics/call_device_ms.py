"""Milliseconds per call that the device was busy in the traced window
outside the program's ``repro.solver_engine`` spans: each call's set-up,
unpack and certificate.  With ``loop_device_ms`` it accounts for all of
the window's device time."""
from bench.harness import program_spans


def read(run):
    spans = program_spans.from_program(run)
    if not spans:
        return None
    return program_spans.call_device_ms(run.trace, spans,
                                        program_spans.calls(run))
