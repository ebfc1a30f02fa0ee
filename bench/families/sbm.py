"""The stochastic block model of arXiv:2010.14159 §5: clusters joined
with probability ``p_in`` inside and ``p_out`` across, each cluster's
nodes measuring one weight vector (copied from this repository's
``sbm_regression`` scenario, so that the benchmark's data cannot move
with the program).  Every tenant shares the one graph and draws its own
data."""
from __future__ import annotations

import numpy as np

from bench.harness.deploy import Deployment, canonical_edges, \
    regression_tenant


def build(cfg: dict, rng: np.random.Generator) -> Deployment:
    sizes = [int(s) for s in cfg["cluster_sizes"]]
    V = sum(sizes)
    assign = np.concatenate([np.full(s, c) for c, s in enumerate(sizes)])
    iu, ju = np.triu_indices(V, k=1)
    p = np.where(assign[iu] == assign[ju], cfg["p_in"], cfg["p_out"])
    keep = rng.random(len(iu)) < p
    edges, weights = canonical_edges(
        np.stack([iu[keep], ju[keep]], axis=1),
        np.full(int(keep.sum()), cfg["edge_weight"], np.float32), V)
    w_true = np.asarray(cfg["cluster_weights"], np.float32)[assign]
    tenants = [regression_tenant(rng, w_true, cfg["samples_per_node"],
                                 cfg["num_labeled"], cfg["label_noise"])
               for _ in range(cfg["tenants"])]
    return Deployment(num_nodes=V, edges=edges, weights=weights,
                      tenants=tenants)
