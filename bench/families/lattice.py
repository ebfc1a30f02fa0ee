"""A ``side`` x ``side`` four-neighbour lattice with weights constant
on each quadrant (this repository's ``grid2d`` data model, copied so
that the benchmark's data cannot move with the program)."""
from __future__ import annotations

import numpy as np

from bench.harness.deploy import Deployment, canonical_edges, \
    regression_tenant


def build(cfg: dict, rng: np.random.Generator) -> Deployment:
    side = int(cfg["side"])
    idx = np.arange(side * side).reshape(side, side)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    edges, weights = canonical_edges(
        np.concatenate([right, down]),
        np.full(len(right) + len(down), cfg["edge_weight"], np.float32),
        side * side)
    rr, cc = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    quad = ((rr >= side // 2).astype(np.int64) * 2
            + (cc >= side // 2)).ravel()
    levels = np.asarray(cfg["quadrant_weights"], np.float32)
    num_labeled = max(int(side * side * cfg["labeled_fraction"]), 4)
    tenants = [regression_tenant(rng, levels[quad],
                                 cfg["samples_per_node"], num_labeled,
                                 cfg["label_noise"])
               for _ in range(cfg["tenants"])]
    return Deployment(num_nodes=side * side, edges=edges, weights=weights,
                      tenants=tenants)
