"""Milliseconds per request in which the device was not busy: each
request's client-side wall time less the device's busy time inside it,
averaged over the requests of the traced window (the serving host path:
the service, the solver's dispatch and set-up, the certificate)."""


def read(run):
    if run.trace is None or not run.events:
        return None
    host = [e.seconds - run.trace.busy_in(run.trace_ns(e.start),
                                          run.trace_ns(e.end))
            for e in run.events]
    return 1e3 * sum(host) / len(host)
