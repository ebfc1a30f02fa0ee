"""Offline calls of ``Solver.run`` on one problem, one after another.

Each call solves the first tenant's problem cold from zeros on the tol
path with the certificate on, as a user's call has it, under a budget
of ``iters_per_call`` iterations.  Where the budget is below the
iterations a cold solve needs, every call does the same work.

Check: ``check_calls`` calls drawn from the seed, the one that ran the
most iterations among them, against the reference run from zeros for
as many iterations: the largest gap in w and in u, both over max |w_ref|
(u's rounding follows sigma D w, whose size is w's), and the relative gap of the last residual the call reported
to the reference's over the same block.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.harness import system
from bench.harness.served import worse
from bench.harness.record import Event


@dataclasses.dataclass
class State:
    problem: object
    solver: object
    results: list


def setup(ctx):
    cfg, mix = ctx.config, ctx.traffic
    dep = ctx.deployment
    problem = system.problem(cfg, system.graph(dep), dep.tenants[0])
    solver = system.solver(system.solver_config(
        cfg, num_iters=mix["iters_per_call"], dtype=ctx.dtype))
    import jax
    for _ in range(mix["warm_calls"]):
        jax.block_until_ready(solver.run(problem))
    return State(problem=problem, solver=solver, results=[])


def window(ctx, st: State, deadline: float):
    import jax
    events = []
    while True:
        start = time.perf_counter()
        try:
            with ctx.span("solver_run"):
                res = st.solver.run(st.problem)
                jax.block_until_ready(res)
            its, ok = int(res.diagnostics["iterations"]), True
        except Exception as exc:       # a failed call is counted, not fatal
            print(f"call failed: {exc!r}")
            res, its, ok = None, 0, False
        end = time.perf_counter()
        events.append(Event(start=start, end=end, iterations=its, ok=ok))
        st.results.append(res)
        if end >= deadline:
            return events


def answers(ctx, st: State, events):
    done = [i for i, r in enumerate(st.results) if r is not None]
    if not done:
        return []
    most = max(done, key=lambda i: events[i].iterations)
    picked = ctx.sample(len(done), ctx.traffic["check_calls"],
                        include=(done.index(most),))
    out = []
    for i in (done[j] for j in picked):
        r = st.results[i]
        out.append({"w": np.asarray(r.w), "u": np.asarray(r.u),
                     "iterations": events[i].iterations,
                     "residual": float(r.residual[-1])})
    return out


def check(ctx, answers) -> dict:
    if not answers:
        return {}
    ref, cfg = ctx.reference, ctx.config
    ten = ctx.deployment.tenants[0]
    params = ref.prox_params(ten.x, ten.y, ten.labeled)
    V, n = ten.w_true.shape
    E = ctx.deployment.num_edges
    runs = {}
    worst = {"w_err": 0.0, "u_err": 0.0, "res_err": 0.0}
    for a in answers:
        its = a["iterations"]
        if its not in runs:
            w, u, res = ref.run(params, np.zeros((V, n), np.float32),
                                np.zeros((E, n), np.float32), its,
                                last_block=cfg["metric_every"])
            runs[its] = (np.asarray(w), np.asarray(u), float(res))
        w_ref, u_ref, res_ref = runs[its]
        scale = float(np.max(np.abs(w_ref)))
        worst["w_err"] = worse(worst["w_err"], float(
            np.max(np.abs(a["w"] - w_ref))) / scale)
        worst["u_err"] = worse(worst["u_err"], float(
            np.max(np.abs(a["u"] - u_ref))) / scale)
        worst["res_err"] = worse(worst["res_err"],
                                 abs(a["residual"] - res_ref) / res_ref)
    return worst
