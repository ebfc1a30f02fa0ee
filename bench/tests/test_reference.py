"""The plain reference agrees with the program's ``dense`` backend for
the same iterations from the same state (CPU, small sizes)."""
import json
import os

import numpy as np
import pytest

from conftest import ROOT, SBM_TINY, TINY


def _config(name):
    if name == "sbm":
        return dict(SBM_TINY)
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TINY[f"configs/{name}.json"])
    return cfg


def _family(name):
    from bench.harness.spec import Spec
    return Spec(ROOT).module("families", name)


@pytest.mark.parametrize("config", ["lattice_512", "sbm"])
def test_reference_matches_dense_backend(config):
    import jax.numpy as jnp

    from bench.harness import system
    from bench.harness.spec import Spec
    from repro.api import Solver, SolverConfig

    cfg = _config(config)
    dep = _family(cfg["family"]).build(cfg, np.random.default_rng(7))
    ten = dep.tenants[0]
    ref = Spec(ROOT).module("references", cfg["reference"]).Reference(
        dep.edges, dep.weights, dep.num_nodes, cfg["lam"], cfg["rho"])
    problem = system.problem(cfg, system.graph(dep), ten)
    rng = np.random.default_rng(8)
    V, n = ten.w_true.shape
    w0 = rng.standard_normal((V, n)).astype(np.float32)
    u0 = np.clip(rng.standard_normal((dep.num_edges, n)), -cfg["lam"],
                 cfg["lam"]).astype(np.float32)
    # float32 rounding over 40 iterations of w, u of order 1: 2e-5
    iters = 40
    res = Solver(SolverConfig(backend="dense", num_iters=iters,
                              metric_every=iters, rho=cfg["rho"],
                              record_residual=True)).run(
        problem, w0=jnp.asarray(w0), u0=jnp.asarray(u0))
    w, u, r = ref.run(ref.prox_params(ten.x, ten.y, ten.labeled), w0, u0,
                      iters)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w),
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res.u), np.asarray(u),
                               rtol=0, atol=2e-5)
    assert float(res.residual[-1]) == pytest.approx(float(r), rel=1e-3)


def test_families_match_the_programs_generators():
    """The benchmark's copies draw what the program's scenario builders
    draw from the same seed."""
    from repro.scenarios import get_scenario
    from repro.scenarios.zoo import lattice_dataset

    cfg = _config("lattice_512")
    dep = _family("lattice").build(cfg, np.random.default_rng(3))
    ds = lattice_dataset(np.random.default_rng(3), cfg["side"])
    np.testing.assert_array_equal(
        dep.edges, np.stack([np.asarray(ds.graph.src),
                             np.asarray(ds.graph.dst)], axis=1))
    np.testing.assert_array_equal(dep.tenants[0].y, np.asarray(ds.data.y))
    np.testing.assert_array_equal(dep.tenants[0].labeled,
                                  np.asarray(ds.data.labeled_mask))

    cfg = _config("sbm")
    cfg["cluster_sizes"], cfg["num_labeled"] = [150, 150], 30
    dep = _family("sbm").build(cfg, np.random.default_rng(3))
    inst = get_scenario("sbm_regression").build(seed=3)
    g = inst.problem.graph
    np.testing.assert_array_equal(
        dep.edges, np.stack([np.asarray(g.src), np.asarray(g.dst)], axis=1))
    np.testing.assert_array_equal(dep.tenants[0].x,
                                  np.asarray(inst.problem.data.x))
    np.testing.assert_array_equal(dep.tenants[0].labeled,
                                  np.asarray(inst.problem.data.labeled_mask))
