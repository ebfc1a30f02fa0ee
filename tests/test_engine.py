"""The engine layer: one step, many executors, one stopping rule.

Locks the PR's architectural invariants:

  * the Pallas fused kernel is *pinned* to the canonical
    ``repro.engine.step.pd_step`` — its interpret-mode output is bitwise
    the engine step evaluated through a window executor,
  * the federated mailbox executor realizes the same D / D^T operators
    as the dense executor in synchronous mode,
  * ``SolverConfig.tol`` early-stops *identically* (same stopping
    iteration) across the dense and federated backends, and within one
    metric chunk on the fused/sharded ones,
  * the engine-unlocked loss x backend combinations (lasso/logistic/tv2
    on the fused pallas path) really take the fused path instead of
    silently falling back to the unfused dense engine.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Solver, SolverConfig
from repro.api.backends import _should_fuse
from repro.api.losses import SquaredLoss
from repro.api.regularizers import TotalVariation
from repro.core.graph import edge_ends_store, plan_edge_blocks, sbm_graph
from repro.core.mesh import make_host_mesh
from repro.data.synthetic import make_sbm_regression
from repro.engine import DenseExecutor, MailboxExecutor, WindowExecutor
from repro.engine import pd_residual, pd_step
from repro.engine.executors import _bf16_parts, _exact_dot
from repro.kernels import ops
from repro.scenarios import get_scenario


def _whole_graph_window(v=48, n=2, seed=3):
    """A single-block layout plus the canonical-step operands for it."""
    rng = np.random.default_rng(seed)
    g, _ = sbm_graph(rng, (v // 2, v - v // 2), p_in=0.4, p_out=0.05)
    lt = plan_edge_blocks(g)                  # small graph -> one block
    assert lt.num_blocks == 1 and lt.kn == 1 and lt.klo == lt.khi == 0
    real = (lt.weights > 0.0).astype(jnp.float32)
    deg = jnp.zeros(lt.nodes_pad).at[lt.src].add(real).at[lt.dst].add(real)
    tau = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 1.0)[:, None]
    w = jnp.asarray(rng.standard_normal((lt.nodes_pad, n)), jnp.float32)
    u = jnp.asarray(0.1 * rng.standard_normal((lt.edges_pad, n)),
                    jnp.float32)
    p = jnp.asarray(rng.standard_normal((lt.nodes_pad, n, n)) * 0.1
                    + np.eye(n), jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal((lt.nodes_pad, n)),
                    jnp.float32)
    sigma = jnp.full((lt.edges_pad, 1), 0.5, jnp.float32)
    la = (1e-2 * lt.weights)[:, None]
    return lt, g, w, u, p, b, tau, sigma, la


@pytest.mark.parametrize("rho", [1.0, 1.9])
def test_pallas_kernel_is_bitwise_the_engine_step(rho):
    """Bit-parity: the in-kernel Pallas copy of the iteration is locked
    to ``engine.pd_step`` (evaluated through a WindowExecutor)."""
    from repro.kernels.pd_step import fused_pd_step

    lt, _, w, u, p, b, tau, sigma, la = _whole_graph_window()
    loss, reg = SquaredLoss(), TotalVariation()

    ends = edge_ends_store(lt.src, lt.dst, 0, 0, lt.block_edges)
    executor = WindowExecutor.from_endpoints(
        ends, lt.nodes_pad, la, klo=0, block_edges=lt.block_edges)
    params = {"b": b, "p": p}

    def prox(v):
        return loss.prox_apply(params, v)

    w_eng, u_eng = pd_step(executor, prox, reg, 1.0, tau, sigma, w, u,
                           rho=rho)
    w_k, u_k = fused_pd_step(
        w, u, ends, (b, p), tau, sigma, la, loss=loss, reg=reg,
        pkeys=("b", "p"), block_nodes=lt.block_nodes,
        block_edges=lt.block_edges, kn=1, klo=0, khi=0, rho=rho,
        interpret=True)
    # the kernel body IS engine.pd_step (same Python function on the
    # loaded window); XLA may fuse the incidence contractions
    # differently inside the interpreted kernel, so parity is exact up
    # to 1 ulp of the contraction — assert that, plus that almost all
    # entries are bit-identical.
    assert float(jnp.max(jnp.abs(w_k - w_eng))) <= 1e-6
    assert float(jnp.max(jnp.abs(u_k - u_eng))) <= 1e-6
    w_same = np.mean(np.asarray(w_k) == np.asarray(w_eng))
    u_same = np.mean(np.asarray(u_k) == np.asarray(u_eng))
    assert w_same >= 0.5 and u_same >= 0.5, (w_same, u_same)


def _f32_bound(a64: np.ndarray, x64: np.ndarray) -> np.ndarray:
    """Worst-case f32 rounding of the product ``a64 @ x64`` summed in
    f32: (terms + 2) units of f32 roundoff over the sum of |terms|."""
    terms = np.abs(a64) @ (np.abs(x64) > 0)
    return (terms + 2) * 2.0 ** -24 * (np.abs(a64) @ np.abs(x64))


def _spread(rng, shape, spread: bool) -> np.ndarray:
    """Standard normal values, or values whose magnitudes span about
    2^-10 to 2^10, so that all three bf16 parts of most are non-zero."""
    x = rng.standard_normal(shape)
    return x * 2.0 ** rng.uniform(-10, 10, shape) if spread else x


@pytest.mark.parametrize("bv,n,spread", [
    (16, 3, False), (512, 3, False),
    (16, 1, True), (16, 2, True), (16, 43, True), (512, 2, True)])
def test_window_executor_contraction_form_matches_gather_form(bv, n,
                                                               spread):
    """The incidence contractions a compiled TPU kernel runs
    (``mxu=True``) compute the same D^T u and D z, per window, as the
    segment-sum / row-gather form of the reference and interpret mode,
    to f32 rounding; ``bv=16`` gives multi-block windows with halos.
    The MXU form also matches a float64 product to f32 rounding, for
    widths whose three stacked bf16 parts fill part of one lane tile
    (n = 1, 2, 3) or cross into a second (n = 43: 3n = 129), and for
    values whose magnitudes span 2^+-10."""
    rng = np.random.default_rng(11)
    g, _ = sbm_graph(rng, (60, 60), p_in=0.3, p_out=0.03)
    lt = plan_edge_blocks(g, block_nodes=bv)
    BV, EB = lt.block_nodes, lt.block_edges
    nw, ew = lt.kn * BV, (lt.klo + 1 + lt.khi) * EB
    ends = edge_ends_store(lt.src, lt.dst, lt.klo, lt.khi, EB)
    u = jnp.asarray(_spread(rng, (ends.shape[0], n), spread), jnp.float32)
    u = jnp.where((ends[:, 0] == ends[:, 1])[:, None], 0.0, u)  # pad: 0
    z = jnp.asarray(_spread(rng, (nw, n), spread), jnp.float32)
    la = jnp.ones((EB, 1), jnp.float32)
    lo = lt.klo * EB
    for b in range(lt.num_blocks):
        win = ends[b * EB:b * EB + ew] - b * BV
        forms = [WindowExecutor.from_endpoints(
            win, nw, la, klo=lt.klo, block_edges=EB, mxu=mxu)
            for mxu in (False, True)]
        u_win = u[b * EB:b * EB + ew]
        if not spread:
            np.testing.assert_allclose(forms[1].gather_duals(u_win),
                                       forms[0].gather_duals(u_win),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(forms[1].edge_diff(z),
                                       forms[0].edge_diff(z),
                                       rtol=1e-6, atol=1e-6)
        # the signed incidence in float64, endpoints outside the window
        # dropped; with magnitudes from 2^-10 to 2^10 both forms cancel,
        # so each is held to f32 rounding of the float64 product
        e = np.asarray(win)
        inc = np.zeros((ew, nw))
        for j, sign in ((0, 1.0), (1, -1.0)):
            ok = (e[:, j] >= 0) & (e[:, j] < nw)
            inc[np.flatnonzero(ok), e[ok, j]] += sign
        for form in forms:
            for got, a64, x in (
                    (form.gather_duals(u_win), inc.T, u_win),
                    (form.edge_diff(z), inc[lo:lo + EB], z)):
                x64 = np.asarray(x, np.float64)
                err = np.abs(np.asarray(got, np.float64) - a64 @ x64)
                assert np.all(err <= _f32_bound(a64, x64)), float(err.max())


def _three_pass_dot(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """The f32-exact product as three separate bf16 MXU products, one per
    bf16 part of ``x``, summed in order: the oracle for the stacked
    pass."""
    import jax
    out = None
    for part in _bf16_parts(x):
        y = jax.lax.dot_general(a, part, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = y if out is None else out + y
    return out


@pytest.mark.parametrize("n", [1, 2, 43])
@pytest.mark.parametrize("product", ["gather_duals", "edge_diff"])
def test_exact_dot_stacked_pass_is_bitwise_the_three_pass_form(product, n):
    """``_exact_dot`` on the window's 0/+-1 bf16 incidence matrices — the
    (NW, EW) one that D^T u contracts and the owned (EB, NW) rows that
    D z contracts — matches a float64 product to f32 rounding and is
    bitwise the three-product form it replaces: each output column
    accumulates on its own, and the three partial products are summed in
    the same order."""
    rng = np.random.default_rng(n)
    nw, eb, klo = 256, 256, 1
    ew = 3 * eb
    ends = rng.integers(-8, nw + 8, (ew, 2)).astype(np.int32)
    ends[::7, 1] = ends[::7, 0]                  # padding rows: src == dst
    ex = WindowExecutor.from_endpoints(
        jnp.asarray(ends), nw, jnp.ones((eb, 1), jnp.float32), klo=klo,
        block_edges=eb, mxu=True)
    if product == "gather_duals":
        a, x = ex.incidence_t, _spread(rng, (ew, n), True)
    else:
        a, x = ex.owned_incidence, _spread(rng, (nw, n), True)
    x = jnp.asarray(x, jnp.float32)
    got = _exact_dot(a, x)
    a64, x64 = np.asarray(a, np.float64), np.asarray(x, np.float64)
    assert set(np.unique(a64)) <= {-1.0, 0.0, 1.0}
    err = np.abs(np.asarray(got, np.float64) - a64 @ x64)
    assert np.all(err <= _f32_bound(a64, x64)), float(err.max())
    if n > 1:
        # XLA:CPU runs a one-column product as a matrix-vector product,
        # which sums in another order than its matrix-matrix product;
        # there the f32 bound above is the check
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(_three_pass_dot(a, x)))
    np.testing.assert_array_equal(np.asarray(getattr(ex, product)(x)),
                                  np.asarray(got))


def test_mailbox_executor_equals_dense_executor_when_synced():
    """With fresh mirrors/mailboxes (sync mode), the federated executor
    computes the same D^T u and D z as the dense one."""
    ds = make_sbm_regression(seed=1, cluster_sizes=(12, 12), p_in=0.6,
                             p_out=1e-2, num_labeled=6)
    g = ds.graph
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((g.num_edges, 2)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((g.num_nodes, 2)), jnp.float32)
    dense = DenseExecutor(g)
    mailbox = MailboxExecutor(
        g, u_recv=u, z_recv=z[g.dst],
        pos_signs=(g.inc_signs > 0.0)[..., None],
        active_dst=jnp.ones((g.num_edges, 1), bool),
        compress=lambda x: x)
    np.testing.assert_array_equal(np.asarray(dense.gather_duals(u)),
                                  np.asarray(mailbox.gather_duals(u)))
    np.testing.assert_array_equal(np.asarray(dense.edge_diff(z)),
                                  np.asarray(mailbox.edge_diff(z)))


def test_pd_residual_zero_at_fixed_point():
    tau = jnp.asarray([0.5, 0.25])
    sigma = jnp.asarray([0.5, 0.5, 0.5])
    w = jnp.ones((2, 3))
    u = jnp.ones((3, 3))
    assert float(pd_residual(tau, sigma, w, u, w, u)) == 0.0
    assert float(pd_residual(tau, sigma, w, u, w + 1e-2, u)) > 0.0


# ---------------------------------------------------------------------------
# Residual-based early stopping (SolverConfig.tol)
# ---------------------------------------------------------------------------

TOL_CONF = SolverConfig(num_iters=4000, rho=1.9, metric_every=10, tol=5e-3)


@pytest.mark.parametrize("name", ["sbm_regression", "grid2d"])
def test_tol_stops_identically_on_dense_and_federated(name):
    """Acceptance: the same stopping iteration on both backends — the
    residual stream is computed from bitwise-identical iterates."""
    # lam=1e-2: strong enough coupling that the residual reaches the
    # tolerance well inside the iteration budget on both scenarios
    inst = get_scenario(name).build(seed=0, smoke=True, lam=1e-2)
    dense = Solver(TOL_CONF).run(inst.problem)
    fed = Solver(TOL_CONF.replace(backend="federated")).run(inst.problem)
    it_dense = dense.diagnostics["iterations"]
    it_fed = fed.diagnostics["iterations"]
    assert it_dense == it_fed, (name, it_dense, it_fed)
    assert it_dense < TOL_CONF.num_iters, "tol never bit — weak test"
    assert it_dense % TOL_CONF.metric_every == 0
    # traces are truncated to the stopped horizon
    assert dense.objective.shape[0] == it_dense // TOL_CONF.metric_every
    # the iterates track at ulp level (XLA may schedule the residual
    # reduction differently in the two chunk programs)
    np.testing.assert_allclose(np.asarray(dense.w), np.asarray(fed.w),
                               rtol=0, atol=1e-5)


def test_tol_stops_within_one_chunk_on_fused_and_sharded():
    """The fused/sharded iterates differ from dense at ulp level, so
    their stopping iteration may differ by at most one metric chunk."""
    inst = get_scenario("sbm_regression").build(seed=0, smoke=True,
                                                lam=1e-2)
    it_dense = Solver(TOL_CONF).run(inst.problem).diagnostics["iterations"]
    assert it_dense < TOL_CONF.num_iters
    it_fused = Solver(TOL_CONF.replace(
        backend="pallas", fused=True)).run(inst.problem
                                           ).diagnostics["iterations"]
    it_shard = Solver(TOL_CONF.replace(
        backend="sharded", mesh=make_host_mesh(1, 1))).run(
        inst.problem).diagnostics["iterations"]
    me = TOL_CONF.metric_every
    assert abs(it_fused - it_dense) <= me, (it_fused, it_dense)
    assert abs(it_shard - it_dense) <= me, (it_shard, it_dense)


def test_tol_none_keeps_full_horizon():
    inst = get_scenario("sbm_regression").build(seed=0, smoke=True)
    cfg = SolverConfig(num_iters=100, rho=1.9, metric_every=10)
    res = Solver(cfg).run(inst.problem)
    assert res.objective.shape == (10,)
    assert "iterations" not in res.diagnostics


def test_tol_respects_budget_ceiling():
    """An unreachable tolerance runs the full budget and reports it."""
    inst = get_scenario("sbm_regression").build(seed=0, smoke=True)
    cfg = SolverConfig(num_iters=60, rho=1.9, metric_every=20, tol=1e-12)
    res = Solver(cfg).run(inst.problem)
    assert res.diagnostics["iterations"] == 60
    assert res.objective.shape == (3,)


def test_masked_sweep_matches_single_solves_exactly():
    """Satellite S4 acceptance: from identical inits, every lane of the
    masked-vmap sweep stops at *the same iteration* as an independent
    single tol solve and produces *bitwise identical* weights — frozen
    lanes replay the single solve's iterate stream exactly."""
    import jax
    from repro.api.backends import _solve_dense, resolve_kernel_hooks
    from repro.api.solver import _capped, _masked_sweep

    inst = get_scenario("sbm_regression").build(seed=0, smoke=True,
                                                lam=1e-2)
    p = inst.problem
    lams = jnp.array([0.3, 0.003, 0.1, 0.03], jnp.float32)
    L = lams.shape[0]
    clip_fn, affine_fn = resolve_kernel_hooks(p, TOL_CONF, False)
    params = p.loss.prox_setup(p.data, p.graph.primal_stepsizes())
    V, n = p.graph.num_nodes, p.num_features
    E = p.graph.num_edges
    budget = _capped(TOL_CONF.num_iters, TOL_CONF.metric_every)
    _, _, _, iters_b, _ = _masked_sweep(
        p.graph, p.data, lams, jnp.zeros((L, V, n)),
        jnp.zeros((L, E, n)), None, params, TOL_CONF.tol,
        loss=p.loss, reg=p.regularizer, num_iters=budget,
        rho=TOL_CONF.rho, metric_every=TOL_CONF.metric_every,
        clip_fn=clip_fn, affine_fn=affine_fn)
    w_b, _, _, iters_b2, _ = _masked_sweep(
        p.graph, p.data, lams, jnp.zeros((L, V, n)),
        jnp.zeros((L, E, n)), None, params, TOL_CONF.tol,
        loss=p.loss, reg=p.regularizer, num_iters=budget,
        rho=TOL_CONF.rho, metric_every=TOL_CONF.metric_every,
        clip_fn=clip_fn, affine_fn=affine_fn)
    iters = np.asarray(jax.device_get(iters_b))
    np.testing.assert_array_equal(iters, np.asarray(iters_b2))
    assert len(set(iters.tolist())) > 1, "lambdas should stop differently"
    single_cfg = TOL_CONF.replace(num_iters=budget)
    for i, lam in enumerate(np.asarray(lams)):
        s = _solve_dense(p.with_lam(float(lam)), single_cfg,
                         w0=jnp.zeros((V, n)), u0=jnp.zeros((E, n)),
                         clip_fn=clip_fn, affine_fn=affine_fn)
        assert s.diagnostics["iterations"] == int(iters[i]), lam
        assert float(jnp.max(jnp.abs(s.w - w_b[i]))) == 0.0, lam


def test_solve_path_tol_masked_sweep_end_to_end():
    """tol-mode solve_path: per-lambda stopping iterations, truncated
    traces, residual-certified lanes, and fewer total iterations than
    the fixed-budget sweep would pay."""
    from repro.api import solve_path

    inst = get_scenario("sbm_regression").build(seed=0, smoke=True,
                                                lam=1e-2)
    lams = jnp.array([0.3, 0.003, 0.1, 0.03], jnp.float32)
    cfg = TOL_CONF.replace(final_iters=2000)
    res = solve_path(inst.problem, lams, cfg)
    L = lams.shape[0]
    V, n = inst.problem.graph.num_nodes, inst.problem.num_features
    assert res.w.shape == (L, V, n)
    iters = np.asarray(res.diagnostics["iterations"])
    assert iters.shape == (L,) and np.all(iters > 0)
    assert np.all(iters % cfg.metric_every == 0)
    # traces are truncated to the last block any lane ran
    blocks = res.objective.shape[1]
    assert res.objective.shape == (L, blocks)
    assert blocks == int(np.max(iters)) // cfg.metric_every
    # each early-stopped lane's final recorded residual certifies <= tol
    resid = np.asarray(res.residual)
    for i in range(L):
        bi = int(iters[i]) // cfg.metric_every - 1
        assert resid[i, bi] <= cfg.tol, (i, resid[i, bi])
    # the masked sweep's win: total iterations well under L x budget
    assert int(iters.sum()) < L * cfg.final_iters
    # path results agree with independent tol solves at the same lambda
    # (warm-started lanes may certify at a different iterate: residual
    # stopping is init-dependent, so compare at solver-accuracy level)
    s = Solver(cfg.replace(num_iters=2000)).run(
        inst.problem.with_lam(float(lams[0])))
    assert float(jnp.max(jnp.abs(s.w - res.w[0]))) <= 0.1


# ---------------------------------------------------------------------------
# Engine-unlocked loss x backend combinations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sparse_lasso", "clustered_logistic",
                                  "laplacian_smoothing"])
def test_fused_path_engages_for_nonsquared_templates(name):
    """lasso/logistic losses and tv2 must ride the fused engine (not the
    silent unfused-dense fallback the pre-engine code used)."""
    inst = get_scenario(name).build(seed=0, smoke=True)
    cfg = SolverConfig(num_iters=50, rho=1.9, backend="pallas", fused=True)
    # every registered loss is kernel-safe now (the logistic Newton
    # solve runs an explicit unrolled Cholesky instead of
    # jnp.linalg.solve), so the fused gate holds even where the real
    # Pallas kernel — not just the jnp oracle — is the default
    assert inst.problem.loss.kernel_safe, name
    assert _should_fuse(inst.problem, cfg), name


@pytest.mark.parametrize("name", ["sparse_lasso", "clustered_logistic"])
def test_fused_matches_dense_on_nonsquared_losses(name):
    inst = get_scenario(name).build(seed=0, smoke=True)
    cfg = SolverConfig(num_iters=150, rho=1.9)
    dense = Solver(cfg).run(inst.problem)
    fused = Solver(cfg.replace(backend="pallas", fused=True)).run(
        inst.problem)
    assert float(jnp.max(jnp.abs(dense.w - fused.w))) <= 1e-4
    np.testing.assert_allclose(np.asarray(fused.objective),
                               np.asarray(dense.objective),
                               rtol=1e-4, atol=1e-6)

# ---------------------------------------------------------------------------
# REPRO_SOLVER_MAX_ITERS cap (engine.loop.capped)
# ---------------------------------------------------------------------------

def test_capped_uncapped_passthrough(monkeypatch):
    from repro.engine import capped
    monkeypatch.delenv("REPRO_SOLVER_MAX_ITERS", raising=False)
    assert capped(500, 25) == 500
    monkeypatch.setenv("REPRO_SOLVER_MAX_ITERS", "1000")
    assert capped(500, 25) == 500           # under the cap: untouched


def test_capped_clamps_to_metric_multiple(monkeypatch):
    from repro.engine import capped
    monkeypatch.setenv("REPRO_SOLVER_MAX_ITERS", "60")
    # non-divisible cap: largest multiple of metric_every <= cap, never 0
    assert capped(500, 25) == 50
    assert capped(500, 60) == 60
    assert capped(500, 1) == 60
    monkeypatch.setenv("REPRO_SOLVER_MAX_ITERS", "10")
    assert capped(500, 1) == 10             # the CI smoke setting


def test_capped_raises_when_cap_below_metric_every(monkeypatch):
    from repro.engine import capped
    # cap < metric_every used to clamp to 0 iterations and return
    # all-zero "solutions"; it must refuse loudly instead
    monkeypatch.setenv("REPRO_SOLVER_MAX_ITERS", "10")
    with pytest.raises(ValueError, match="metric_every"):
        capped(500, 25)
    monkeypatch.setenv("REPRO_SOLVER_MAX_ITERS", "24")
    with pytest.raises(ValueError, match="metric_every"):
        capped(500, 25)


# ---------------------------------------------------------------------------
# Eq.-11 optimality gap certificate (engine.step.optimality_gap)
# ---------------------------------------------------------------------------

def test_optimality_gap_upper_bounds_suboptimality():
    """The eq.-11 gap P(w) - g(u) is a *certified* upper bound: it must
    dominate the observed suboptimality P(w_k) - P(w_long) at every
    checkpoint, never go (numerically) negative, and shrink as the
    iterates converge."""
    from repro.api import Problem
    from repro.engine import optimality_gap

    ds = make_sbm_regression(seed=2, cluster_sizes=(20, 20), p_in=0.5,
                             p_out=5e-3, num_labeled=10)
    prob = Problem.create(ds.graph, ds.data, 1e-3)

    cfg = SolverConfig(num_iters=4000, rho=1.9)
    long = Solver(cfg).run(prob)
    p_star = float(prob.objective(long.w))

    gaps = []
    for iters in (50, 200, 1000):
        res = Solver(cfg.replace(num_iters=iters)).run(prob)
        gap = float(optimality_gap(prob, res.w, res.u))
        subopt = float(prob.objective(res.w)) - p_star
        assert gap >= subopt - 1e-6, (iters, gap, subopt)
        assert gap >= -1e-6, (iters, gap)
        gaps.append(gap)
    assert gaps[-1] < gaps[0], gaps


def test_psd_pinv_solve_matches_pinv():
    """The certificate's eigendecomposition pseudo-inverse agrees with
    ``jnp.linalg.pinv`` on full-rank, rank-deficient and zero PSD
    blocks."""
    from repro.engine.step import psd_pinv_solve
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 5, 3)).astype(np.float32)
    x[8:16, :, 2] = x[8:16, :, 0]                 # rank 2
    x[16:24] = 0.0                                # zero blocks
    q = jnp.asarray(np.einsum("vmn,vmk->vnk", x, x) / 5.0)
    rhs = jnp.asarray(rng.standard_normal((64, 3)).astype(np.float32))
    want = jnp.einsum("vnk,vk->vn", jnp.linalg.pinv(q), rhs)
    got = psd_pinv_solve(q, rhs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_certificate_reports_optimality_gap_column():
    """Squared+TV diagnostics carry the second certificate column."""
    from repro.engine.step import certificate

    inst = get_scenario("sbm_regression").build(seed=0, smoke=True)
    prob = inst.problem
    res = Solver(SolverConfig(num_iters=200)).run(prob)
    diag = certificate(prob, res.w, res.u)
    assert "optimality_gap" in diag
    assert np.isfinite(float(diag["optimality_gap"]))
