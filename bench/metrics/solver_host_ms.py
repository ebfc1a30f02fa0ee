"""Milliseconds per request inside the program's top-level
``repro.service_*`` spans (``update_session`` and ``solve``) in which
the device ran nothing, averaged over the traced window's requests: the
serving and solver host path, seen from inside."""
from bench.harness import program_spans


def read(run):
    spans = program_spans.from_program(run)
    if not spans:
        return None
    return program_spans.solver_host_ms(run.trace, spans, len(run.events))
