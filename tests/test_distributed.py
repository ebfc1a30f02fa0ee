"""Distributed (shard_map) nLasso solver tests.

The sharded message-passing solver must agree with the single-program
solver exactly (same fixed-point iteration, different communication
pattern).  Multi-device behaviour is exercised in a subprocess with 8
virtual host devices so the main pytest process keeps 1 device (the brief
requires smoke tests to see exactly one).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.distributed import shard_problem, solve_and_unpermute
from repro.core.graph import sbm_graph
from repro.core.nlasso import nlasso
from repro.core.partition import (block_partition, cluster_partition,
                                  plan_partition, permute_node_array,
                                  unpermute_node_array)
from repro.data.synthetic import make_sbm_regression
from repro.core.mesh import make_host_mesh


@pytest.fixture(scope="module")
def ds():
    return make_sbm_regression(seed=3, cluster_sizes=(24, 24), p_in=0.5,
                               p_out=5e-3, num_labeled=12)


def test_sharded_matches_reference_single_shard(ds):
    mesh = make_host_mesh(1, 1)
    w_sharded = solve_and_unpermute(ds.graph, ds.data, mesh, lam=1e-3,
                                    num_iters=150)
    ref = nlasso(ds.graph, ds.data, lam=1e-3, num_iters=150)
    np.testing.assert_allclose(w_sharded, np.asarray(ref.w), atol=2e-4)


def test_boundary_comm_matches_dense(ds):
    mesh = make_host_mesh(1, 1)
    w_dense = solve_and_unpermute(ds.graph, ds.data, mesh, lam=1e-3,
                                  num_iters=100, comm="dense")
    w_bnd = solve_and_unpermute(ds.graph, ds.data, mesh, lam=1e-3,
                                num_iters=100, comm="boundary")
    np.testing.assert_allclose(w_bnd, w_dense, atol=2e-4)


def test_partition_plan_roundtrip(ds):
    g = ds.graph
    assign = cluster_partition(g, 4)
    plan = plan_partition(g, assign, 4)
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((g.num_nodes, 3)).astype(np.float32)
    packed = permute_node_array(plan, arr)
    back = unpermute_node_array(plan, packed, g.num_nodes)
    np.testing.assert_allclose(back, arr)
    # every real node appears exactly once
    perm = plan.node_perm[plan.node_perm >= 0]
    assert sorted(perm) == list(range(g.num_nodes))


def test_cluster_partition_cuts_fewer_edges_than_block():
    rng = np.random.default_rng(7)
    g, _ = sbm_graph(rng, (40, 40, 40, 40), p_in=0.5, p_out=5e-3)
    a_blk = block_partition(g.num_nodes, 4)
    a_cls = cluster_partition(g, 4)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    # node ids are cluster-ordered in the SBM generator, so block partition
    # is already strong; cluster partitioning must be comparable or better
    # on a scrambled ordering
    perm = rng.permutation(g.num_nodes)
    from repro.core.graph import build_graph
    g2 = build_graph(np.stack([perm[src], perm[dst]], 1),
                     np.asarray(g.weights), g.num_nodes)
    a_blk2 = block_partition(g2.num_nodes, 4)
    a_cls2 = cluster_partition(g2, 4)
    s2, d2 = np.asarray(g2.src), np.asarray(g2.dst)
    cut_blk = int(np.sum(a_blk2[s2] != a_blk2[d2]))
    cut_cls = int(np.sum(a_cls2[s2] != a_cls2[d2]))
    assert cut_cls < cut_blk, (cut_cls, cut_blk)


def test_shard_problem_preserves_edge_weights(ds):
    prob = shard_problem(ds.graph, ds.data, 2)
    valid = prob.plan.edge_perm >= 0
    np.testing.assert_allclose(
        np.sort(np.asarray(prob.bound_unit)[valid]),
        np.sort(np.asarray(ds.graph.weights)))


# ---------------------------------------------------------------------------
# Two-level (hierarchical) layout invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hier4(ds):
    from repro.core.partition import plan_hierarchy
    assign = cluster_partition(ds.graph, 4)
    return plan_hierarchy(ds.graph, assign, 4)


def test_hierarchy_ownership_is_a_partition(ds, hier4):
    """Every node and every edge is owned by exactly one shard."""
    h = hier4
    owned_nodes = h.node_map[h.node_owned > 0]
    assert sorted(owned_nodes.tolist()) == list(range(ds.graph.num_nodes))
    owned_edges = h.edge_map[h.edge_owned > 0]
    assert sorted(owned_edges.tolist()) == list(range(ds.graph.num_edges))


def test_hierarchy_reorder_unpermute_identity(ds, hier4):
    """inject -> extract is the identity on node and (oriented) edge
    signals, for any shard count's stacked store layout."""
    h = hier4
    rng = np.random.default_rng(0)
    w = rng.standard_normal((ds.graph.num_nodes, 3)).astype(np.float32)
    w_store = np.zeros((h.w_inj.shape[0], 3), np.float32)
    valid = h.w_inj >= 0
    w_store[valid] = w[h.w_inj[valid]]
    np.testing.assert_array_equal(w_store[h.w_sel], w)

    u = rng.standard_normal((ds.graph.num_edges, 3)).astype(np.float32)
    u_store = np.zeros((h.u_inj.shape[0], 3), np.float32)
    validu = h.u_inj >= 0
    u_store[validu] = u[h.u_inj[validu]] * h.u_inj_flip[validu, None]
    np.testing.assert_array_equal(u_store[h.u_sel] * h.u_flip[:, None], u)


def test_hierarchy_halo_closure_covers_owned_incidence(ds, hier4):
    """Each shard's local subgraph reproduces D^T u exactly on its owned
    nodes from local storage alone (the 1-hop halo closure invariant the
    per-iteration dual refresh relies on)."""
    h = hier4
    g = ds.graph
    rng = np.random.default_rng(1)
    u = rng.standard_normal((g.num_edges, 2)).astype(np.float32)
    dtu = np.zeros((g.num_nodes, 2), np.float32)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    np.add.at(dtu, src, u)
    np.add.at(dtu, dst, -u)
    NV, NE, ESR = h.nodes_pad, h.edges_pad, h.u_store_rows
    u_store = np.zeros((h.u_inj.shape[0], 2), np.float32)
    valid = h.u_inj >= 0
    u_store[valid] = u[h.u_inj[valid]] * h.u_inj_flip[valid, None]
    for s in range(h.num_shards):
        rows = slice(s * NE, (s + 1) * NE)
        real = h.weights[rows] > 0
        ust = u_store[s * ESR + h.klo * h.block_edges:][:NE][real]
        contrib = np.zeros((NV, 2), np.float32)
        np.add.at(contrib, h.src[rows][real], ust)
        np.add.at(contrib, h.dst[rows][real], -ust)
        own = h.node_owned[s * NV:(s + 1) * NV] > 0
        gids = h.node_map[s * NV:(s + 1) * NV][own]
        np.testing.assert_allclose(contrib[own], dtu[gids], atol=1e-5)


def test_hierarchy_single_shard_solve_matches_dense(ds):
    """reorder -> fused solve -> unpermute is the dense iteration."""
    from repro.api import Problem, Solver, SolverConfig

    prob = Problem.create(ds.graph, ds.data, 1e-3)
    r_dense = Solver(SolverConfig(backend="dense", num_iters=150)).run(prob)
    r_hier = Solver(SolverConfig(backend="sharded_fused",
                                 num_iters=150)).run(prob)
    np.testing.assert_allclose(np.asarray(r_hier.w), np.asarray(r_dense.w),
                               atol=2e-4)
    assert "halo_exchange_bytes" in r_hier.diagnostics


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    from repro.core.distributed import solve_and_unpermute
    from repro.core.nlasso import nlasso
    from repro.data.synthetic import make_sbm_regression
    from repro.core.mesh import make_host_mesh

    ds = make_sbm_regression(seed=3, cluster_sizes=(24, 24), p_in=0.5,
                             p_out=5e-3, num_labeled=12)
    mesh = make_host_mesh(8, 1)
    out = {}
    for comm in ("dense", "boundary"):
        w = solve_and_unpermute(ds.graph, ds.data, mesh, lam=1e-3,
                                num_iters=150, comm=comm)
        ref = nlasso(ds.graph, ds.data, lam=1e-3, num_iters=150)
        out[comm] = float(np.max(np.abs(w - np.asarray(ref.w))))
    print(json.dumps(out))
""")


def test_sharded_solver_8_virtual_devices(ds):
    """End-to-end 8-way shard_map run in a subprocess (own XLA_FLAGS)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    errs = json.loads(res.stdout.strip().splitlines()[-1])
    assert errs["dense"] < 2e-4, errs
    assert errs["boundary"] < 2e-4, errs


HIER_MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    from repro.core.distributed import (shard_problem_fused,
                                        solve_nlasso_hier)
    from repro.core.mesh import make_host_mesh
    from repro.core.nlasso import nlasso
    from repro.data.synthetic import make_sbm_regression

    ds = make_sbm_regression(seed=3, cluster_sizes=(24, 24), p_in=0.5,
                             p_out=5e-3, num_labeled=12)
    ref = np.asarray(nlasso(ds.graph, ds.data, lam=1e-3, num_iters=150).w)
    out = {"rerun_bitwise": True, "vs_dense": 0.0, "comms": []}
    for num_shards in (2, 4, 8):
        mesh = make_host_mesh(num_shards, 1)
        sp = shard_problem_fused(ds.graph, ds.data, num_shards, seed=0)
        w, u, it, comm = solve_nlasso_hier(sp, mesh, 1e-3, 150)
        w2, _, _, _ = solve_nlasso_hier(sp, mesh, 1e-3, 150)
        out["rerun_bitwise"] &= bool(np.array_equal(np.asarray(w),
                                                    np.asarray(w2)))
        out["vs_dense"] = max(out["vs_dense"],
                              float(np.max(np.abs(np.asarray(w) - ref))))
        out["comms"].append(comm)
    print(json.dumps(out))
""")


def test_hierarchical_determinism_across_shard_counts(ds):
    """The hierarchical fused solve is bitwise-reproducible at every
    shard count on CPU, and shard-count-independent to f32 rounding
    (different per-shard layouts reorder single additions)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", HIER_MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["rerun_bitwise"], out
    assert out["vs_dense"] < 1e-4, out
    # the small-graph fixture has a low cut fraction: comm="auto" must
    # have picked boundary exchange at low shard counts
    assert out["comms"][0] == "boundary", out


DEFAULT_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    from repro.api import Problem, Solver, SolverConfig
    from repro.core.mesh import make_device_mesh, make_host_mesh
    from repro.data.synthetic import make_sbm_regression

    ds = make_sbm_regression(seed=3, cluster_sizes=(24, 24), p_in=0.5,
                             p_out=5e-3, num_labeled=12)
    prob = Problem.create(ds.graph, ds.data, 1e-3)
    out = {"mesh_devices": int(make_device_mesh().devices.size)}
    for backend in ("sharded", "sharded_fused"):
        cfg = SolverConfig(backend=backend, num_iters=20, comm="dense")
        implicit = Solver(cfg).run(prob).diagnostics
        explicit = Solver(cfg.replace(mesh=make_host_mesh(4, 1))).run(
            prob).diagnostics
        one = Solver(cfg.replace(mesh=make_host_mesh(1, 1))).run(
            prob).diagnostics
        key = "halo_exchange_bytes_per_iter"
        out[backend] = [implicit[key], explicit[key], one[key]]
    print(json.dumps(out))
""")


def test_sharded_backends_default_to_every_device():
    """With no mesh in the config, both sharded backends spread the
    graph over every device of the process (4 virtual devices here):
    the exchange volume is the explicit 4-device mesh's, not one
    device's."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", DEFAULT_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["mesh_devices"] == 4
    for backend in ("sharded", "sharded_fused"):
        implicit, explicit, one = out[backend]
        assert implicit == explicit != one, (backend, out[backend])


def test_sharded_fused_refuses_a_window_over_the_vmem_cap(ds, monkeypatch):
    """A per-shard layout whose fused window exceeds the VMEM cap is an
    error naming both numbers, never a kernel the chip would refuse."""
    from repro.api import Problem, Solver, SolverConfig
    monkeypatch.setenv("REPRO_FUSED_MAX_WINDOW_BYTES", "4096")
    prob = Problem.create(ds.graph, ds.data, 1e-3)
    with pytest.raises(ValueError, match=r"needs \d+ bytes of VMEM.*cap "
                                         r"is 4096 bytes"):
        Solver(SolverConfig(backend="sharded_fused", num_iters=10,
                            mesh=make_host_mesh(1, 1))).run(prob)
