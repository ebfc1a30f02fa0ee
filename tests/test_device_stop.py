"""Device-resident early stopping: one host transfer per tol solve.

The tol engines (dense and fused) drive a ``lax.while_loop`` over
metric-cadence blocks with the eq.-11 residual carried in device memory;
the *only* device->host transfer a tol solve performs is the single
explicit ``jax.device_get`` that fetches the stopping iteration (the
trace buffers come back as lazily-sliced device arrays).  These tests
pin that transfer contract:

  * ``jax.transfer_guard_device_to_host("disallow")`` turns any
    *implicit* transfer (``float(residual)``-style host syncs of the old
    chunk loop) into an error,
  * a monkeypatched ``jax.device_get`` counts the explicit fetches and
    asserts exactly one.

Also here: the in-kernel residual (the extra (nb, 1) f32 output of the
fused Pallas kernel) against the jnp oracle and a by-hand eq.-11
computation on the kernel's own inputs/outputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Solver, SolverConfig
from repro.kernels import ref
from repro.scenarios import get_scenario

from test_kernels import _fused_step_args

TOL_CONF = SolverConfig(num_iters=4000, rho=1.9, metric_every=10,
                        tol=5e-3, compute_diagnostics=False)


def _count_device_gets(monkeypatch):
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    return calls


@pytest.mark.parametrize("backend", ["dense", "pallas_fused"])
def test_tol_solve_is_one_transfer(backend, monkeypatch):
    """Acceptance: a tol solve performs exactly one device->host
    transfer, on dense and on the fused path."""
    inst = get_scenario("sbm_regression").build(seed=0, smoke=True,
                                                lam=1e-2)
    if backend == "pallas_fused":
        cfg = TOL_CONF.replace(backend="pallas", fused=True)
    else:
        cfg = TOL_CONF
    # warm the compile cache outside the guard: compilation is free to
    # inspect host values, the steady-state solve is not
    Solver(cfg).run(inst.problem)

    calls = _count_device_gets(monkeypatch)
    with jax.transfer_guard_device_to_host("disallow"):
        res = Solver(cfg).run(inst.problem)
    assert len(calls) == 1, f"{backend}: {len(calls)} explicit fetches"
    # the one fetch carried the stopping iteration
    it = res.diagnostics["iterations"]
    assert isinstance(it, int)
    assert 0 < it < cfg.num_iters
    # traces were truncated on device (lazy slices, no extra sync)
    assert res.objective.shape[0] == it // cfg.metric_every


def test_tol_none_never_syncs_per_chunk(monkeypatch):
    """Satellite S2: a fixed-budget (tol=None) chunked solve performs no
    implicit per-chunk residual syncs."""
    inst = get_scenario("sbm_regression").build(seed=0, smoke=True)
    cfg = SolverConfig(num_iters=100, rho=1.9, metric_every=10,
                       compute_diagnostics=False)
    Solver(cfg).run(inst.problem)
    calls = _count_device_gets(monkeypatch)
    with jax.transfer_guard_device_to_host("disallow"):
        res = Solver(cfg).run(inst.problem)
    assert len(calls) == 0, "tol=None must not fetch anything"
    assert res.objective.shape == (10,)


# ---------------------------------------------------------------------------
# in-kernel residual: Pallas extra output vs oracle vs by-hand eq. 11
# ---------------------------------------------------------------------------

def _manual_residual(args, kw, w_new, u_new):
    """eq.-11 residual over owned rows, straight from kernel in/out."""
    w_store, u_store, tau, sigma = args[0], args[1], args[4], args[5]
    eb, klo = kw["block_edges"], kw["klo"]
    nb = sigma.shape[0] // eb
    bv = kw["block_nodes"]
    f32 = np.float32
    w0 = np.asarray(w_store, f32)[:nb * bv]
    t0 = np.asarray(tau, f32)[:nb * bv]
    u0 = np.asarray(u_store, f32)[klo * eb:(klo + nb) * eb]
    rp = np.max(np.abs(np.asarray(w_new, f32) - w0) / t0)
    rd = np.max(np.abs(np.asarray(u_new, f32) - u0) / np.asarray(sigma, f32))
    return max(rp, rd)


@pytest.mark.parametrize("v,n,bv", [(61, 2, 16), (40, 4, 64)])
def test_in_kernel_residual_matches_oracle_and_manual(v, n, bv):
    from repro.kernels.pd_step import fused_pd_step
    args, kw = _fused_step_args(v, n, bv, seed=v)
    w_k, u_k, res_k = fused_pd_step(*args, **kw, compute_residual=True,
                                    interpret=True)
    w_r, u_r, res_r = ref.fused_pd_step_ref(*args, **kw,
                                            compute_residual=True)
    assert res_k.dtype == jnp.float32 and res_r.dtype == jnp.float32
    np.testing.assert_allclose(float(res_k), float(res_r),
                               rtol=1e-6, atol=1e-6)
    manual = _manual_residual(args, kw, w_r, u_r)
    np.testing.assert_allclose(float(res_r), manual, rtol=1e-6, atol=1e-6)
    # the residual output does not perturb the step itself
    w_p, u_p = fused_pd_step(*args, **kw, interpret=True)
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_p))
    np.testing.assert_array_equal(np.asarray(u_k), np.asarray(u_p))


def test_in_kernel_residual_multi_iteration_running_max():
    """iters > 1 (whole-graph-in-VMEM fusion): the kernel accumulates
    the running max of the per-iteration residuals."""
    from repro.kernels.pd_step import fused_pd_step
    args, kw = _fused_step_args(48, 2, None, seed=4)   # one block
    w_k, u_k, res_k = fused_pd_step(*args, **kw, iters=5,
                                    compute_residual=True, interpret=True)
    _, _, res_r = ref.fused_pd_step_ref(*args, **kw, iters=5,
                                        compute_residual=True)
    np.testing.assert_allclose(float(res_k), float(res_r),
                               rtol=1e-6, atol=1e-6)
    # running max over iterations >= the residual of the final step alone
    w4, u4 = ref.fused_pd_step_ref(*args, **kw, iters=4)
    ext = args[0].shape[0] - w4.shape[0]
    w4s = jnp.concatenate([w4, args[0][w4.shape[0]:]]) if ext else w4
    _, _, res_last = ref.fused_pd_step_ref(w4s, u4, *args[2:], **kw,
                                           compute_residual=True)
    assert float(res_r) >= float(res_last) - 1e-6
