"""A deployment as plain arrays: the graph and each tenant's data.

Families (``bench/families/<family>.py``) build one from a
configuration and the seed; the reference reads it as it is, and
:mod:`bench.harness.system` hands it to the program under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Tenant:
    """One tenant's local datasets: x (V, m, n), y (V, m), the labeled
    mask (V,) and the true weights (V, n) its labels are measured from."""

    x: np.ndarray
    y: np.ndarray
    labeled: np.ndarray
    w_true: np.ndarray


@dataclasses.dataclass
class Deployment:
    """The empirical graph (edges canonical: src < dst, sorted,
    deduplicated) and the tenants that share it."""

    num_nodes: int
    edges: np.ndarray            # (E, 2) int64
    weights: np.ndarray          # (E,) float32
    tenants: list[Tenant]

    @property
    def num_edges(self) -> int:
        return int(self.edges.shape[0])


def canonical_edges(edges, weights, num_nodes: int):
    """Edges as src < dst, sorted by (src, dst), duplicates dropped
    (the first weight kept)."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    weights = np.asarray(weights, np.float32)
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    if np.any(lo == hi):
        raise ValueError("self-loop in the empirical graph")
    order = np.lexsort((hi, lo))
    lo, hi, weights = lo[order], hi[order], weights[order]
    key = lo * num_nodes + hi
    keep = np.concatenate([[True], key[1:] != key[:-1]]) if len(key) else \
        np.zeros(0, bool)
    return np.stack([lo[keep], hi[keep]], axis=1), weights[keep]


def regression_tenant(rng: np.random.Generator, w_true: np.ndarray,
                      samples: int, num_labeled: int,
                      noise: float) -> Tenant:
    """Local linear-regression data y = x w_true + noise * eps with
    x ~ N(0, I) and ``num_labeled`` nodes drawn for the training set
    (the draws in the order the source's generator makes them)."""
    w_true = np.asarray(w_true, np.float32)
    V, n = w_true.shape
    x = rng.standard_normal((V, samples, n)).astype(np.float32)
    y = np.einsum("vmn,vn->vm", x, w_true)
    if noise > 0:
        y = y + np.float32(noise) * rng.standard_normal(y.shape).astype(
            np.float32)
    labeled = np.zeros(V, np.float32)
    labeled[rng.choice(V, size=num_labeled, replace=False)] = 1.0
    return Tenant(x=x, y=y.astype(np.float32), labeled=labeled,
                  w_true=w_true)
