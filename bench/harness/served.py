"""What the serving loops share: sessions over one graph, and the
check of served answers against the reference.

The check takes ``check_sample`` responses drawn from the seed, the one
that ran the most iterations among them.  For each, the reference takes
the tenant's data as the benchmark left it after that request and the
response's (w, u), and computes one iteration's eq.-11 residual; the
largest over tol is ``resid_ratio``.  A response whose own residual
missed tol is counted in ``failed``.
"""
from __future__ import annotations

import math

import numpy as np

from bench.harness import system


def open_sessions(ctx, config):
    """A ``SolveService`` with one session per tenant over one graph."""
    dep = ctx.deployment
    graph = system.graph(dep)
    svc = system.service(config)
    sessions = [svc.create_session(f"tenant{t}",
                                   system.problem(ctx.config, graph, ten))
                for t, ten in enumerate(dep.tenants)]
    return svc, sessions


def certify(svc, sessions, attempts: int = 12) -> None:
    """Solve each session until a response certifies, at most
    ``attempts`` times: cold first, then warm, each under the
    configuration's per-request budget.  (A cold solve's residual can
    stall for several budgets before it falls, so no progress rule.)"""
    for sid in sessions:
        for _ in range(attempts):
            if svc.solve(sid).meets_sla:
                break


def warm_trace_slices(config: dict) -> None:
    """Compile, for every block count a tol solve can stop at, the
    eager slices the program takes of its trace buffers (``[:nb]``, then
    the last entry).  Their shapes depend on where a solve stops, so
    without this a request that stops at a new block count compiles
    inside the window."""
    import jax.numpy as jnp
    blocks = config["budget_iters"] // config["metric_every"]
    buf = jnp.zeros((blocks,), jnp.float32)
    for nb in range(1, blocks + 1):
        buf[:nb][-1].block_until_ready()


def answers(ctx, served, events):
    """The sampled responses as host arrays, with the data they answer.
    ``served[i]`` is (request, response or None, the session's u)."""
    done = [i for i, (_, resp, _) in enumerate(served) if resp is not None]
    if not done:
        return []
    most = max(done, key=lambda i: events[i].iterations)
    out = []
    for j in ctx.sample(len(done), ctx.traffic["check_sample"],
                        include=(done.index(most),)):
        req, resp, u = served[done[j]]
        out.append({"tenant": req.tenant, "y": req.y_after,
                    "w": np.asarray(resp.w), "u": np.asarray(u)})
    return out


def worse(a: float, b: float) -> float:
    """The larger, and not a number where either is not one."""
    return math.nan if math.isnan(a) or math.isnan(b) else max(a, b)


def check(ctx, answers) -> dict:
    if not answers:
        return {}
    ref, tol = ctx.reference, ctx.config["tol"]
    worst = 0.0
    for a in answers:
        ten = ctx.deployment.tenants[a["tenant"]]
        params = ref.prox_params(ten.x, a["y"], ten.labeled)
        worst = worse(worst, ref.residual(params, a["w"], a["u"]) / tol)
    return {"resid_ratio": worst}
