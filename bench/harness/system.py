"""The one module that hands a deployment to the program under test.

Loops build the program's objects here (``Problem``, ``SolverConfig``,
``SolveService``) and call its entry points
themselves; nothing else in the benchmark imports the program.
"""
from __future__ import annotations

import os
import sys


def import_program(root: str) -> None:
    """Put the program's ``src`` of the checkout at ``root`` on the
    path (the benchmark runs from the checkout, not an installed copy)."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def graph(dep):
    from repro.core.graph import build_graph
    return build_graph(dep.edges, dep.weights, dep.num_nodes)


def problem(cfg: dict, graph_obj, tenant):
    """The program's ``Problem`` over ``graph_obj`` with ``tenant``'s
    data (every sample real)."""
    import jax.numpy as jnp

    from repro.api import Problem
    from repro.core.losses import NodeData
    data = NodeData(x=jnp.asarray(tenant.x), y=jnp.asarray(tenant.y),
                    sample_mask=jnp.ones(tenant.y.shape, jnp.float32),
                    labeled_mask=jnp.asarray(tenant.labeled))
    return Problem.create(graph_obj, data, lam=cfg["lam"],
                          loss=cfg["loss"], regularizer=cfg["regularizer"])


def solver_config(cfg: dict, **over):
    """The configuration's solver settings; ``over`` replaces any."""
    from repro.api import SolverConfig
    kw = dict(backend=cfg["backend"], num_iters=cfg["budget_iters"],
              metric_every=cfg["metric_every"], rho=cfg["rho"],
              tol=cfg["tol"], dtype=cfg["dtype"])
    kw.update(over)
    return SolverConfig(**kw)


def service(config):
    from repro.serving import SolveService
    return SolveService(config)


def data_delta(nodes, y_rows):
    from repro.serving import DataDelta
    return DataDelta(nodes=tuple(int(v) for v in nodes), y=y_rows)


def solver(config):
    from repro.api import Solver
    return Solver(config)
