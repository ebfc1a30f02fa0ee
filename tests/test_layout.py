"""Edge-blocked layout pass (core.graph.plan_edge_blocks) + fused solver.

The fused primal-dual kernel trusts the layout's structural guarantees
(owner-contiguous edge ranges, halo windows covering every incident edge
of owned + halo nodes, orientation flips on relabeled duals).  These
tests pin those guarantees directly on the arrays, check the permutation
machinery round-trips bit-for-bit, and check the fused solve agrees with
the dense engine on odd / non-block-multiple graph sizes.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.api import Problem, Solver, SolverConfig
from repro.core import losses as L
from repro.core.graph import (build_graph, chain_graph, plan_edge_blocks,
                              sbm_graph)
from repro.core.partition import rcm_order


def make_problem(v, seed=0, n=2, lam=5e-3, graph=None):
    rng = np.random.default_rng(seed)
    if graph is None:
        graph, _ = sbm_graph(rng, (v // 2, v - v // 2), p_in=0.3, p_out=0.02)
    w_true = rng.standard_normal((v, n)).astype(np.float32)
    x = rng.standard_normal((v, 4, n)).astype(np.float32)
    y = np.einsum("vmn,vn->vm", x, w_true)
    lab = np.zeros(v, np.float32)
    lab[rng.choice(v, max(v // 5, 2), replace=False)] = 1.0
    data = L.NodeData(x=jnp.asarray(x), y=jnp.asarray(y),
                      sample_mask=jnp.ones((v, 4), jnp.float32),
                      labeled_mask=jnp.asarray(lab))
    return Problem.create(graph, data, lam=lam)


# ---------------------------------------------------------------------------
# structural invariants
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("v,bv", [(103, 32), (64, 16), (257, 64), (37, None)])
def test_layout_structure(v, bv):
    rng = np.random.default_rng(v)
    g, _ = sbm_graph(rng, (v // 2, v - v // 2), p_in=0.3, p_out=0.03)
    lt = plan_edge_blocks(g, block_nodes=bv)
    BV, EB, nb = lt.block_nodes, lt.block_edges, lt.num_blocks
    assert nb * BV >= v
    src = np.asarray(lt.src)
    dst = np.asarray(lt.dst)
    wts = np.asarray(lt.weights)
    real = wts > 0
    assert real.sum() == g.num_edges
    # canonical orientation + owner-contiguity: each real edge lives in the
    # block of its (smaller) src endpoint
    assert np.all(src[real] < dst[real])
    owner = np.arange(nb).repeat(EB)
    assert np.all(src[real] // BV == owner[real])
    # halo guarantee (a): dst endpoints inside the node window
    assert np.all(dst[real] < owner[real] * BV + lt.kn * BV)
    # halo guarantee (b): every incident edge of owned + halo nodes inside
    # the edge window of the owning block (storage ids, window start b*EB)
    store = np.arange(lt.edges_pad) + lt.klo * EB   # owned slot -> storage
    ew = (lt.klo + 1 + lt.khi) * EB
    for b in range(nb):
        own = np.arange(b * BV, (b + 1) * BV)
        halo = dst[b * EB:(b + 1) * EB][real[b * EB:(b + 1) * EB]]
        nodes = np.unique(np.concatenate([own, halo]))
        e = store[real & (np.isin(src, nodes) | np.isin(dst, nodes))]
        if len(e):
            assert e.min() >= b * EB and e.max() < b * EB + ew, b


def test_rcm_order_is_a_permutation_and_reduces_bandwidth():
    rng = np.random.default_rng(3)
    g = chain_graph(rng, 101)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    shuf = rng.permutation(101)
    g2 = build_graph(np.stack([shuf[src], shuf[dst]], 1),
                     np.asarray(g.weights), 101)
    order = rcm_order(np.asarray(g2.src), np.asarray(g2.dst), 101)
    assert sorted(order.tolist()) == list(range(101))
    inv = np.empty(101, np.int64)
    inv[order] = np.arange(101)
    bw = np.max(np.abs(inv[np.asarray(g2.src)] - inv[np.asarray(g2.dst)]))
    assert bw <= 2  # a path graph relabels back to (near-)unit bandwidth


# ---------------------------------------------------------------------------
# permutation machinery: reorder -> unpermute round-trips bit-for-bit
# ---------------------------------------------------------------------------
def test_layout_permutes_round_trip_bitwise():
    rng = np.random.default_rng(7)
    g, _ = sbm_graph(rng, (33, 30), p_in=0.3, p_out=0.05)
    lt = plan_edge_blocks(g, block_nodes=16)
    w = rng.standard_normal((g.num_nodes, 3)).astype(np.float32)
    u = rng.standard_normal((g.num_edges, 3)).astype(np.float32)
    # node round trip
    perm = np.asarray(lt.node_perm)
    w_l = np.zeros((lt.nodes_pad, 3), np.float32)
    w_l[perm >= 0] = w[perm[perm >= 0]]
    back = np.asarray(jnp.take(jnp.asarray(w_l), lt.node_inv, axis=0))
    assert np.array_equal(back, w)
    # edge round trip with orientation flips
    flip = np.asarray(lt.edge_flip)
    pos = np.asarray(lt.edge_pos)
    u_l = np.zeros((lt.edges_pad, 3), np.float32)
    u_l[pos] = u * flip[:, None]
    back_u = u_l[pos] * flip[:, None]
    assert np.array_equal(back_u, u)
    # layout endpoints/weights are the relabeled originals
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    inv = np.asarray(lt.node_inv)
    lo = np.minimum(inv[src], inv[dst])
    hi = np.maximum(inv[src], inv[dst])
    assert np.array_equal(np.asarray(lt.src)[pos], lo)
    assert np.array_equal(np.asarray(lt.dst)[pos], hi)
    assert np.array_equal(np.asarray(lt.weights)[pos], np.asarray(g.weights))


# ---------------------------------------------------------------------------
# solves: fused-vs-dense on awkward sizes, determinism, reorder invariance
# ---------------------------------------------------------------------------
CFG = SolverConfig(num_iters=200, rho=1.9)


@pytest.mark.parametrize("v,bv", [(103, 32), (37, None), (130, 64)])
def test_fused_matches_dense_on_odd_sizes(v, bv):
    problem = make_problem(v, seed=v)
    if bv is not None:
        problem = Problem(graph=problem.graph.with_layout(block_nodes=bv),
                          data=problem.data, lam=problem.lam,
                          loss=problem.loss,
                          regularizer=problem.regularizer)
    dense = Solver(CFG).run(problem)
    fused = Solver(CFG.replace(backend="pallas", fused=True)).run(problem)
    assert float(jnp.max(jnp.abs(dense.w - fused.w))) <= 1e-4
    np.testing.assert_allclose(np.asarray(fused.objective),
                               np.asarray(dense.objective),
                               rtol=1e-4, atol=1e-6)


def test_fused_solve_is_deterministic_bitwise():
    """reorder -> solve -> unpermute is bit-reproducible on the reference
    path (the layout adds no run-to-run nondeterminism)."""
    problem = make_problem(77, seed=5)
    cfg = CFG.replace(backend="pallas", fused=True)
    a = Solver(cfg).run(problem)
    b = Solver(cfg).run(problem)
    assert np.array_equal(np.asarray(a.w), np.asarray(b.w))
    assert np.array_equal(np.asarray(a.u), np.asarray(b.u))
    assert np.array_equal(np.asarray(a.objective), np.asarray(b.objective))


def test_reordered_solve_unpermutes_to_unreordered_solve():
    """Relabeling the graph by the layout's RCM order, solving, and
    mapping back agrees with solving the original ordering (the layout
    pass changes summation order only, never the optimization problem)."""
    problem = make_problem(64, seed=9)
    g = problem.graph
    lt = plan_edge_blocks(g, block_nodes=16)
    inv = np.asarray(lt.node_inv)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    g2 = build_graph(np.stack([inv[src], inv[dst]], 1),
                     np.asarray(g.weights), g.num_nodes)
    perm = np.empty_like(inv)
    perm[inv] = np.arange(len(inv))
    d = problem.data
    data2 = L.NodeData(x=d.x[perm], y=d.y[perm],
                       sample_mask=d.sample_mask[perm],
                       labeled_mask=d.labeled_mask[perm])
    p2 = Problem(graph=g2, data=data2, lam=problem.lam, loss=problem.loss,
                 regularizer=problem.regularizer)
    res1 = Solver(CFG).run(problem)
    res2 = Solver(CFG).run(p2)
    w_back = np.asarray(res2.w)[inv]
    np.testing.assert_allclose(w_back, np.asarray(res1.w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(res2.final_objective),
                               float(res1.final_objective), rtol=1e-5)


def test_fused_solve_path_matches_dense_path():
    """Lambda sweeps ride the fused engine (backend='pallas', fused=True)
    and agree with the dense-path sweep pointwise."""
    from repro.api.solver import solve_path
    problem = make_problem(103, seed=3)
    lams = [1e-3, 3e-3, 1e-2]
    fused = solve_path(problem, lams,
                       SolverConfig(rho=1.9, backend="pallas", fused=True))
    dense = solve_path(problem, lams, SolverConfig(rho=1.9))
    assert fused.w.shape == dense.w.shape
    assert float(jnp.max(jnp.abs(fused.w - dense.w))) <= 1e-4
    np.testing.assert_allclose(np.asarray(fused.objective),
                               np.asarray(dense.objective),
                               rtol=1e-4, atol=1e-6)


def test_fused_warm_start_and_continuation_match_dense():
    problem = make_problem(90, seed=11)
    cfgf = CFG.replace(backend="pallas", fused=True)
    d0 = Solver(CFG).run(problem)
    f0 = Solver(cfgf).run(problem)
    d1 = Solver(CFG).run(problem, w0=d0.w, u0=d0.u)
    f1 = Solver(cfgf).run(problem, w0=f0.w, u0=f0.u)
    assert float(jnp.max(jnp.abs(d1.w - f1.w))) <= 1e-4
    assert float(jnp.max(jnp.abs(d1.u - f1.u))) <= 1e-4


def test_pallas_route_is_reported(monkeypatch):
    """``diagnostics["route"]`` says which kernels ran: the fused window
    with its extents and VMEM estimate, or — when the estimate exceeds
    the cap — the unfused route, with the numbers that decided it."""
    problem = make_problem(103, seed=3)
    cfg = CFG.replace(backend="pallas", fused=True, num_iters=20,
                      metric_every=10)
    route = Solver(cfg).run(problem).diagnostics["route"]
    assert route["fused"] and route["window_bytes"] <= route["window_cap"]
    assert route["num_blocks"] >= 1 and route["kn"] >= 1
    monkeypatch.setenv("REPRO_FUSED_MAX_WINDOW_BYTES", "4096")
    problem = make_problem(103, seed=4)       # a fresh graph: re-planned
    route = Solver(cfg).run(problem).diagnostics["route"]
    assert not route["fused"]
    assert route["window_bytes"] > route["window_cap"] == 4096
    assert Solver(cfg.replace(fused=False)).run(
        problem).diagnostics["route"] == {"fused": False}
