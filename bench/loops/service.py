"""A closed loop of one client against ``SolveService``, one request at
a time: apply the request's data delta (``update_session``), then a
warm ``solve`` to the configuration's tol, certificate included.

Set-up admits every tenant's session over one shared graph and solves
each until it certifies, so that every request of the window starts
warm, then sends one request through the window's path to warm its
programs.  The check
is :mod:`bench.harness.served`'s.
"""
from __future__ import annotations

import dataclasses
import time

from bench.harness import served, system
from bench.harness.record import Event
from bench.harness.traffic import Stream


@dataclasses.dataclass
class State:
    service: object
    sessions: list
    stream: Stream
    served: list            # (request, response, u) per event


def setup(ctx):
    config = system.solver_config(ctx.config, dtype=ctx.dtype)
    svc, sessions = served.open_sessions(ctx, config)
    served.certify(svc, sessions)
    ctx.log("setup: sessions certified")
    served.warm_trace_slices(ctx.config)
    stream = Stream(ctx.traffic, ctx.config, ctx.deployment, ctx.seed)
    st = State(service=svc, sessions=sessions, stream=stream, served=[])
    _serve(ctx, st, stream.next())
    st.served.clear()
    return st


def _serve(ctx, st: State, req):
    """One request; its response, or None where the service raised (a
    failed request is counted, not fatal)."""
    sid = st.sessions[req.tenant]
    try:
        with ctx.span("request"):
            with ctx.span("update_session"):
                st.service.update_session(
                    sid, delta=system.data_delta(req.nodes, req.y_rows))
            with ctx.span("solve"):
                resp = st.service.solve(sid)
    except Exception as exc:
        print(f"request failed: {exc!r}")
        resp = None
    st.served.append((req, resp, st.service.session(sid).u))
    return resp


def window(ctx, st: State, deadline: float):
    events = []
    while True:
        start = time.perf_counter()
        resp = _serve(ctx, st, st.stream.next())
        end = time.perf_counter()
        events.append(Event(start=start, end=end,
                            iterations=resp.iterations if resp else 0,
                            ok=bool(resp and resp.meets_sla)))
        if end >= deadline:
            return events


def answers(ctx, st: State, events):
    return served.answers(ctx, st.served, events)


def check(ctx, answers) -> dict:
    return served.check(ctx, answers)
