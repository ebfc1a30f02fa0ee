"""The one traffic generator: reads a mix's parameters, draws requests
from the seed.

A request re-measures the labels of ``remeasure_fraction`` of one
tenant's nodes: fresh draws y = x w_true + ``remeasure_noise`` * eps at
nodes drawn without replacement, so the data stays stationary (no
random walk).  The tenant is drawn uniformly from the deployment's.
Every seed gives requests of the same size; the seed picks which
tenants and nodes, and the noise.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    """One request: the tenant, the nodes re-measured and their new
    labels, and that tenant's labels as they stand after it."""

    tenant: int
    nodes: np.ndarray            # (k,) sorted int64
    y_rows: np.ndarray           # (k, m) float32
    y_after: np.ndarray          # (V, m) float32


class Stream:
    def __init__(self, mix: dict, cfg: dict, deployment, seed: int):
        self.dep = deployment
        # a stream of its own: the deployment draws from the seed itself
        self.rng = np.random.default_rng([seed, 1])
        V = deployment.num_nodes
        self.k = max(int(round(mix["remeasure_fraction"] * V)), 1)
        self.noise = np.float32(cfg["remeasure_noise"])
        self.y = [t.y for t in deployment.tenants]

    def next(self) -> Request:
        """The next request."""
        tenant = int(self.rng.integers(len(self.dep.tenants)))
        ten = self.dep.tenants[tenant]
        nodes = np.sort(self.rng.choice(self.dep.num_nodes, size=self.k,
                                        replace=False))
        x = ten.x[nodes]
        rows = (np.einsum("kmn,kn->km", x, ten.w_true[nodes])
                + self.noise * self.rng.standard_normal(
                    x.shape[:2]).astype(np.float32)).astype(np.float32)
        y = self.y[tenant].copy()
        y[nodes] = rows
        self.y[tenant] = y
        return Request(tenant=tenant, nodes=nodes, y_rows=rows, y_after=y)
