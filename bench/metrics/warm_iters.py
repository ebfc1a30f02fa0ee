"""Mean iterations a warm-started request ran to its tol
(``SolveResponse.iterations``)."""


def read(run):
    if not run.events:
        return None
    return sum(e.iterations for e in run.events) / len(run.events)
