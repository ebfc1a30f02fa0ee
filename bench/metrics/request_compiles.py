"""Mean JAX compiles (backend compiles and compilation-cache loads)
inside each ``repro.service_solve`` span of the traced window: the
``compiles`` of each request's ``SolveResponse``."""
from bench.harness import program_spans


def read(run):
    spans = program_spans.from_program(run)
    if not spans:
        return None
    return program_spans.request_compiles(spans)
