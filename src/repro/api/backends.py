"""Execution backends for the unified solver — thin drivers over the engine.

Four registered backends, all running the *same* canonical primal-dual
iteration (:func:`repro.engine.step.pd_step`, paper eqs. 14-15) through
backend-specific executors, and returning one
:class:`~repro.api.problem.SolveResult`:

  * ``dense``     — single-program ``lax.scan`` over the dense executor
                    (jit-compatible, differentiable, the CPU/GPU/TPU
                    default),
  * ``pallas``    — the dense path with the TPU kernels auto-wired, or
                    (default on TPU) the fused primal-dual kernel whose
                    in-kernel body runs the canonical step on a VMEM
                    window executor,
  * ``sharded``   — the ``shard_map`` halo-exchange realization in
                    ``core.distributed`` (graph partitioned over a device
                    mesh, collectives per iteration),
  * ``federated`` — the round-based federated runtime in
                    ``repro.federated`` (per-node clients exchanging
                    edge messages; partial participation, local updates,
                    compression, and a communication-cost ledger).

``SolverConfig.tol`` enables residual-based early stopping on every
backend: the horizon advances in ``metric_every``-sized metric blocks
and stops at the first block whose eq.-11 fixed-point residual
(:func:`repro.engine.step.pd_residual`) is <= tol.  Identical iterates
produce identical residual streams, so dense and federated_sync stop at
the same iteration.  The dense/fused/batched engines drive the blocks
*on-device* (:func:`repro.engine.loop.device_loop`: one
``lax.while_loop`` program, residual never leaves device memory, and the
fused path computes it in-kernel) — a tol solve performs exactly one
device->host transfer, the final fetch of the stopping iteration.  The
federated backend keeps the host chunk loop
(:func:`repro.engine.loop.run_chunked`): its checkpoint schedule is a
Python hook that must fire between chunks.

``register_backend`` makes new execution strategies reachable from
``Solver.run`` without touching call sites.
"""
from __future__ import annotations

import dataclasses
import os
import weakref
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.losses import Loss, SquaredLoss
from repro.api.problem import Problem, SolveResult, SolverConfig
from repro.api.regularizers import Regularizer, TotalVariation
from repro.core.graph import (edge_ends_store, fused_window_bytes,
                              fused_window_cap, graph_signal_mse)
from repro.core.losses import NodeData
from repro.core.partition import gather_padded
from repro.engine import (DenseExecutor, certificate, device_loop,
                          pd_residual, scan_solve)
from repro.engine import pd_step as engine_pd_step
from repro.kernels import ops
from repro import obs
from repro.obs import device_fetch

BACKENDS: dict[str, Callable] = {}


def _jit(fn, *, static_argnames, donate_argnums=()):
    """jit wrapper requesting buffer donation where the backend supports
    it (TPU/GPU), so warm-started carries stop copying.  Donation is a
    no-op (with a warning) on CPU, so it is skipped there.  The backend
    query happens lazily at the first call, not at import.

    Donation contract: arrays passed in donated positions (``w0``/``u0``)
    are consumed — callers must not reuse them after the solve.
    """
    cache: dict[bool, Callable] = {}

    def wrapper(*args, **kwargs):
        donate = jax.default_backend() in ("tpu", "gpu")
        if donate not in cache:
            cache[donate] = jax.jit(
                fn, static_argnames=static_argnames,
                donate_argnums=donate_argnums if donate else ())
        return cache[donate](*args, **kwargs)

    return wrapper


def register_backend(name: str):
    """Decorator adding ``fn(problem, config, *, w0, u0, w_true)``."""
    def deco(fn):
        BACKENDS[name] = fn
        return fn
    return deco


def get_backend(name: str) -> Callable:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {sorted(BACKENDS)}")


# ---------------------------------------------------------------------------
# Engine adapters (the iteration math itself lives in repro.engine.step)
# ---------------------------------------------------------------------------

def pd_iteration(graph, prox: Callable, regularizer: Regularizer, lam,
                 tau: jnp.ndarray, sigma: jnp.ndarray, w: jnp.ndarray,
                 u: jnp.ndarray, *, clip_fn: Callable | None = None):
    """One primal-dual step on the dense executor.

    Compatibility adapter over the canonical
    :func:`repro.engine.step.pd_step` — kept so the legacy
    ``core.nlasso.pd_step`` shim and FedTV's personalization update keep
    their historical signature.
    """
    return engine_pd_step(DenseExecutor(graph), prox, regularizer, lam,
                          tau, sigma, w, u, clip_fn=clip_fn)


def _diagnostics(problem: Problem, w, u, config: SolverConfig) -> dict:
    """Certificate per config — empty for throwaway (warm-phase) solves.

    The ``certificate`` span holds the eager certificate's dispatch; its
    device time lands where its values are first read."""
    if not config.compute_diagnostics:
        return {}
    with obs.span("certificate"):
        return certificate(problem, w, u)


def _check_cadence(config: SolverConfig) -> None:
    if config.num_iters % config.metric_every:
        raise ValueError(
            f"metric_every={config.metric_every} must divide "
            f"num_iters={config.num_iters}")


def _storage_dtype(config: SolverConfig, *, fused: bool) -> str:
    """Validate ``SolverConfig.dtype`` for the chosen execution path.

    Returns the canonical dtype name.  bf16 is a *fused-path* storage
    policy (state stored bf16, accumulation f32 — see
    ``kernels.ref.pd_window_step``); every other path runs f32 and
    rejects a reduced dtype loudly instead of silently ignoring it.
    """
    dt = jnp.dtype(config.dtype)
    if dt == jnp.dtype(jnp.float32):
        return "float32"
    if dt == jnp.dtype(jnp.bfloat16):
        if not fused:
            raise NotImplementedError(
                "SolverConfig.dtype='bfloat16' is a storage policy of "
                "the fused pallas path; this path runs float32 (use "
                "backend='pallas' with fused=True, or dtype='float32')")
        return "bfloat16"
    raise ValueError(
        f"unsupported SolverConfig.dtype {config.dtype!r}; use "
        "'float32' or 'bfloat16'")


def _with_iterations(diag: dict, config: SolverConfig,
                     iterations: int) -> dict:
    """Record iterations-to-tolerance on tol runs (host-side ints)."""
    if config.tol is not None and diag is not None:
        diag = dict(diag)
        diag["iterations"] = int(iterations)
    return diag


# ---------------------------------------------------------------------------
# Dense backend (single-program lax.scan) + Pallas kernel wiring
# ---------------------------------------------------------------------------

def make_metrics_fn(loss: Loss, reg: Regularizer, graph, data, lam, w_true):
    """``metrics(w) -> (objective, mse)`` — the one trace formula.

    Shared by the dense/pallas scan engines and the federated runtime so
    their objective/MSE traces are the same expression (the conformance
    suite compares them bitwise).  MSE is the paper's eq. (24) over the
    unlabeled (test) nodes, 0 when no ground truth is supplied.
    """
    unlabeled = 1.0 - data.labeled_mask

    def metrics(w):
        obj = loss.empirical_error(data, w) + reg.value(graph, w, lam)
        if w_true is None:
            mse = jnp.float32(0.0)
        else:
            mse = graph_signal_mse(w, w_true, unlabeled)
        return obj, mse

    return metrics


def _dense_scan_impl(graph, data, lam, w0, u0, w_true, *, loss: Loss,
                     reg: Regularizer, num_iters: int, rho: float,
                     metric_every: int, clip_fn, affine_fn,
                     record_residual: bool = False):
    """The jitted engine: scan Algorithm 1, recording metrics on a cadence.

    ``loss``/``reg`` are static (hashable frozen dataclasses), so repeated
    solves of equally-templated problems share one trace.  ``w0``/``u0``
    are donated (where the backend supports it), so warm-started
    continuation solves re-use the carry buffers instead of copying.
    """
    tau = graph.primal_stepsizes()
    sigma = graph.dual_stepsizes()
    prox = loss.make_prox(data, tau, affine_fn=affine_fn)
    metrics = make_metrics_fn(loss, reg, graph, data, lam, w_true)
    executor = DenseExecutor(graph)

    def run_block(state, iters):
        del iters                      # dense blocks advance one step
        w, u = state
        return engine_pd_step(executor, prox, reg, lam, tau, sigma, w, u,
                              rho=rho, clip_fn=clip_fn)

    residual_fn = None
    if record_residual:
        def residual_fn(prev, new):
            return pd_residual(tau, sigma, prev[0], prev[1], new[0],
                               new[1])

    (w, u), traces = scan_solve(
        run_block, lambda s: metrics(s[0]), (w0, u0),
        num_iters=num_iters, metric_every=metric_every,
        residual_fn=residual_fn)
    if record_residual:
        (obj_trace, mse_trace), res_trace = traces
    else:
        (obj_trace, mse_trace), res_trace = traces, None
    return w, u, obj_trace, mse_trace, res_trace


_dense_scan = _jit(_dense_scan_impl,
                   static_argnames=("loss", "reg", "num_iters", "rho",
                                    "metric_every", "clip_fn", "affine_fn",
                                    "record_residual"),
                   donate_argnums=(3, 4))


def _dense_block_fn(graph, data, lam, w_true, params, *, loss: Loss,
                    reg: Regularizer, rho: float, metric_every: int,
                    clip_fn, affine_fn):
    """Build ``run_block(state)`` for the device-resident tol driver:
    ``metric_every`` engine steps, metrics, and the block-max residual.

    ``params`` is the loss's prox parameter pytree, precomputed *once*
    per solve by the caller (the block runs many times per solve and
    must not redo the per-node setup — e.g. the squared loss's batched
    matrix inverse — on every trip); None falls back to ``make_prox``
    for opaque losses without a ``prox_setup``.
    """
    tau = graph.primal_stepsizes()
    sigma = graph.dual_stepsizes()
    if params is None:
        prox = loss.make_prox(data, tau, affine_fn=affine_fn)
    else:
        def prox(v):
            return loss.prox_apply(params, v, affine_fn=affine_fn)
    metrics = make_metrics_fn(loss, reg, graph, data, lam, w_true)
    executor = DenseExecutor(graph)

    def step(state, _):
        w, u = state
        new = engine_pd_step(executor, prox, reg, lam, tau, sigma, w, u,
                             rho=rho, clip_fn=clip_fn)
        return new, pd_residual(tau, sigma, w, u, new[0], new[1])

    def run_block(state):
        state, res = jax.lax.scan(step, state, None, length=metric_every)
        obj, mse = metrics(state[0])
        # block-max residual: robust stopping signal (a single small
        # step — e.g. an idle federated round — must not read as
        # convergence); it doubles as the certificate trace entry
        res = jnp.max(res)
        return state, (obj, mse, res), res

    return run_block


def _dense_tol_impl(graph, data, lam, w0, u0, w_true, params, tol, *,
                    loss: Loss, reg: Regularizer, num_iters: int,
                    rho: float, metric_every: int, clip_fn, affine_fn):
    """The jitted device-resident tol engine: one ``lax.while_loop``
    program over metric blocks, the eq.-11 residual carried on device
    (see :func:`repro.engine.loop.device_loop`).  ``tol`` is a traced
    operand, so tolerances share one executable.  Returns
    ``(w, u, obj, mse, res, iterations)`` with full-budget trace
    buffers (zeros past the stop) and ``iterations`` a device scalar —
    the caller's single fetch.
    """
    run_block = _dense_block_fn(
        graph, data, lam, w_true, params, loss=loss, reg=reg, rho=rho,
        metric_every=metric_every, clip_fn=clip_fn, affine_fn=affine_fn)
    (w, u), (obj, mse, res), its = device_loop(
        run_block, (w0, u0), num_iters=num_iters,
        metric_every=metric_every, tol=tol)
    return w, u, obj, mse, res, its


_dense_tol = _jit(_dense_tol_impl,
                  static_argnames=("loss", "reg", "num_iters", "rho",
                                   "metric_every", "clip_fn", "affine_fn"),
                  donate_argnums=(3, 4))


def _solve_dense(problem: Problem, config: SolverConfig, *, w0=None, u0=None,
                 w_true=None, clip_fn=None, affine_fn=None) -> SolveResult:
    """Dense-engine driver.  Its spans: ``solver_setup`` (initial state,
    prox set-up), ``solver_engine`` (see :func:`_solve_fused`) and, on
    the tol path, ``solver_unpack`` (the trace buffers cut to the
    stopping block)."""
    _check_cadence(config)
    _storage_dtype(config, fused=False)
    # a 0-iteration budget degenerates to the (0-length) scan; the chunk
    # loop would have no chunks and hence no traces to return
    fixed = config.tol is None or config.num_iters == 0
    with obs.span("solver_setup"):
        V, n = problem.num_nodes, problem.num_features
        if w0 is None:
            w0 = jnp.zeros((V, n), jnp.float32)
        if u0 is None:
            u0 = jnp.zeros((problem.graph.num_edges, n), jnp.float32)
        params = None
        if not fixed:
            # per-solve prox setup happens once, not once per block
            try:
                params = problem.loss.prox_setup(
                    problem.data, problem.graph.primal_stepsizes())
            except NotImplementedError:
                pass
    with obs.span("solver_engine"):
        if fixed:
            w, u, obj, mse, res = _dense_scan(
                problem.graph, problem.data, problem.lam, w0, u0, w_true,
                loss=problem.loss, reg=problem.regularizer,
                num_iters=config.num_iters, rho=config.rho,
                metric_every=config.metric_every, clip_fn=clip_fn,
                affine_fn=affine_fn,
                record_residual=config.record_residual)
            iterations = config.num_iters
        else:
            w, u, obj, mse, res, its = _dense_tol(
                problem.graph, problem.data, problem.lam, w0, u0, w_true,
                params, config.tol, loss=problem.loss,
                reg=problem.regularizer, num_iters=config.num_iters,
                rho=config.rho, metric_every=config.metric_every,
                clip_fn=clip_fn, affine_fn=affine_fn)
            # the solve's single device->host transfer: the stopping
            # iteration; the trace buffers truncate lazily from it
            (iterations,) = device_fetch((its,))
            iterations = int(iterations)
    if not fixed:
        with obs.span("solver_unpack"):
            nb = iterations // config.metric_every
            obj, mse, res = obj[:nb], mse[:nb], res[:nb]
    diag = _with_iterations(_diagnostics(problem, w, u, config), config,
                            iterations)
    return SolveResult(w=w, u=u, objective=obj,
                       mse=None if w_true is None else mse,
                       lam=problem.lam, diagnostics=diag, residual=res)


# ---------------------------------------------------------------------------
# Batched dense engine: many shape-matched problems, one vmapped executable
# ---------------------------------------------------------------------------

def _batched_scan_impl(graph_b, data_b, lam_b, w0_b, u0_b, *, loss: Loss,
                       reg: Regularizer, num_iters: int, rho: float,
                       metric_every: int, clip_fn, affine_fn,
                       record_residual: bool = False):
    """``_dense_scan_impl`` vmapped over a leading batch axis.

    ``graph_b`` is an :class:`EmpiricalGraph` whose array children carry
    a leading batch axis (static aux — node count, template slots — is
    shared), so problems with *different structures* batch together as
    long as their shapes match: structure arrays are traced operands of
    the dense engine, not compile-time constants.
    """
    def one(graph, data, lam, w0, u0):
        return _dense_scan_impl(
            graph, data, lam, w0, u0, None, loss=loss, reg=reg,
            num_iters=num_iters, rho=rho, metric_every=metric_every,
            clip_fn=clip_fn, affine_fn=affine_fn,
            record_residual=record_residual)

    return jax.vmap(one)(graph_b, data_b, lam_b, w0_b, u0_b)


_batched_scan = _jit(_batched_scan_impl,
                     static_argnames=("loss", "reg", "num_iters", "rho",
                                      "metric_every", "clip_fn", "affine_fn",
                                      "record_residual"),
                     donate_argnums=(3, 4))


def _batched_tol_impl(graph_b, data_b, lam_b, w0_b, u0_b, params_b, tol, *,
                      loss: Loss, reg: Regularizer, num_iters: int,
                      rho: float, metric_every: int, clip_fn, affine_fn):
    """Batched device-resident tol engine: one ``lax.while_loop`` trips
    every problem through a metric block and stops when the *max*
    residual over the batch certifies (batch-granular stopping, as
    before — every problem runs the shared iteration count so every
    returned certificate is individually valid).  Traces come back
    (T, B); the caller transposes after truncating at the fetched
    iteration count.
    """
    def one_block(graph, data, lam, params, state):
        run_block = _dense_block_fn(
            graph, data, lam, None, params, loss=loss, reg=reg, rho=rho,
            metric_every=metric_every, clip_fn=clip_fn,
            affine_fn=affine_fn)
        return run_block(state)

    def run_block(state):
        state, (obj, mse, res), _ = jax.vmap(one_block, in_axes=(0, 0, 0,
                                                                 0, 0))(
            graph_b, data_b, lam_b, params_b, state)
        return state, (obj, mse, res), jnp.max(res)

    (w, u), (obj, mse, res), its = device_loop(
        run_block, (w0_b, u0_b), num_iters=num_iters,
        metric_every=metric_every, tol=tol)
    return w, u, obj, mse, res, its


_batched_tol = _jit(_batched_tol_impl,
                    static_argnames=("loss", "reg", "num_iters", "rho",
                                     "metric_every", "clip_fn",
                                     "affine_fn"),
                    donate_argnums=(3, 4))


def _batched_setup_impl(graph_b, data_b, *, loss: Loss):
    def one(graph, data):
        return loss.prox_setup(data, graph.primal_stepsizes())

    return jax.vmap(one)(graph_b, data_b)


# jitted: an eagerly-vmapped prox_setup costs more host dispatches than
# the whole warm chunk it precomputes for
_batched_setup = _jit(_batched_setup_impl, static_argnames=("loss",))


def solve_dense_batched(problem_b: Problem, config: SolverConfig, w0_b,
                        u0_b, *, clip_fn=None, affine_fn=None):
    """Solve B stacked problems as one vmapped dense-engine run.

    ``problem_b`` is a stacked Problem pytree (leading batch axis on
    every array leaf; shared static aux) — see ``api.solver.solve_many``
    for the stacking front-end.  Early stopping is batch-granular: with
    ``tol`` set, the on-device while loop stops when the *max* residual
    over the batch certifies, so every problem runs the shared iteration
    count and every returned certificate is individually valid.

    Returns ``(w, u, obj, mse, res, iterations)`` with leading batch
    axes ((B, T) traces; ``res`` None unless tracked).
    """
    _check_cadence(config)
    _storage_dtype(config, fused=False)
    if config.tol is None or config.num_iters == 0:
        w, u, obj, mse, res = _batched_scan(
            problem_b.graph, problem_b.data, problem_b.lam, w0_b, u0_b,
            loss=problem_b.loss, reg=problem_b.regularizer,
            num_iters=config.num_iters, rho=config.rho,
            metric_every=config.metric_every, clip_fn=clip_fn,
            affine_fn=affine_fn, record_residual=config.record_residual)
        return w, u, obj, mse, res, config.num_iters

    try:
        params_b = _batched_setup(problem_b.graph, problem_b.data,
                                  loss=problem_b.loss)
    except NotImplementedError:
        params_b = None

    w, u, obj, mse, res, its = _batched_tol(
        problem_b.graph, problem_b.data, problem_b.lam, w0_b, u0_b,
        params_b, config.tol, loss=problem_b.loss,
        reg=problem_b.regularizer, num_iters=config.num_iters,
        rho=config.rho, metric_every=config.metric_every,
        clip_fn=clip_fn, affine_fn=affine_fn)
    # the batch's single device->host transfer: the stopping iteration
    (iterations,) = device_fetch((its,))
    nb = int(iterations) // config.metric_every
    return (w, u, obj[:nb].T, mse[:nb].T, res[:nb].T, int(iterations))


def resolve_kernel_hooks(problem: Problem, config: SolverConfig,
                         use_pallas: bool):
    """(clip_fn, affine_fn) for a dense-engine run.

    Caller-supplied hooks from the config always win; the pallas backend
    fills unset ones with the stock TPU kernels (the dual-clip kernel only
    applies to the TV regularizer, the affine kernel to the squared loss).
    """
    clip_fn, affine_fn = config.clip_fn, config.affine_fn
    if use_pallas:
        if clip_fn is None and isinstance(problem.regularizer,
                                          TotalVariation):
            clip_fn = ops.tv_prox
        if affine_fn is None and isinstance(problem.loss, SquaredLoss):
            affine_fn = ops.batched_affine
    return clip_fn, affine_fn


@register_backend("dense")
def solve_dense(problem: Problem, config: SolverConfig, *, w0=None, u0=None,
                w_true=None) -> SolveResult:
    clip_fn, affine_fn = resolve_kernel_hooks(problem, config, False)
    return _solve_dense(problem, config, w0=w0, u0=u0, w_true=w_true,
                        clip_fn=clip_fn, affine_fn=affine_fn)


# ---------------------------------------------------------------------------
# Fused pallas path: edge-blocked layout + fused primal-dual kernel
# ---------------------------------------------------------------------------

# layouts are planned once per graph object (EmpiricalGraph hashes by
# identity, so a WeakKeyDictionary gives per-object caching without
# retaining graphs).  Attaching via graph.with_layout() bypasses this
# cache entirely.
_LAYOUT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _graph_layout(graph, window_hint=None):
    """Plan (or fetch) the graph's edge-blocked layout.

    ``window_hint = (num_features, param_floats, itemsize, cap)`` feeds
    the block-size auto-tuner in ``plan_edge_blocks`` (pick the block
    ladder rung minimizing total window traffic under the VMEM cap).
    The cache keeps whichever layout was planned first for a graph —
    per-object, so one problem's hint never leaks to another graph.
    """
    if graph.layout is not None:
        return graph.layout
    from repro.core.graph import plan_edge_blocks
    layout = _LAYOUT_CACHE.get(graph)
    if layout is None:
        layout = plan_edge_blocks(graph, window_hint=window_hint)
        _LAYOUT_CACHE[graph] = layout
    return layout


def _fused_enabled(config: SolverConfig) -> bool:
    """Fused is the default on TPU; env/flag opt-out (and opt-in off-TPU)."""
    if config.fused is not None:
        return bool(config.fused)
    env = os.environ.get("REPRO_FUSED")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "no", "off")
    return jax.default_backend() == "tpu"


def _fused_supported(problem: Problem, config: SolverConfig) -> bool:
    """The fused step needs windowable prox parameters and an
    edge-elementwise dual resolvent.

    Any registered loss qualifies through ``prox_setup`` (an opaque
    ``CallableLoss`` does not); losses whose ``prox_apply`` cannot lower
    inside a Pallas TPU kernel (``kernel_safe=False``, e.g. the logistic
    Newton loop) still fuse wherever the jnp reference path runs.
    Custom kernel hooks disable fusion (they target the unfused engine).
    """
    loss, reg = problem.loss, problem.regularizer
    has_setup = type(loss).prox_setup is not Loss.prox_setup
    kernel_ok = (not ops._use_kernel_default()) or loss.kernel_safe
    return (has_setup and kernel_ok and reg.fusable
            and config.clip_fn is None and config.affine_fn is None)


def _param_floats(problem: Problem) -> int | None:
    """The loss's per-node prox words in a VMEM window, or None for a
    custom loss that gives no estimate."""
    try:
        return problem.loss.prox_param_floats(problem.data.x.shape[1],
                                              problem.num_features)
    except NotImplementedError:
        return None


def _fused_window(problem: Problem, config: SolverConfig | None = None):
    """Plan (or fetch) the graph's layout and size its VMEM window.

    Returns ``(layout, window_bytes, cap)``; ``window_bytes`` is None for
    a custom loss with no VMEM estimate (which then takes the unfused
    route rather than crash the dispatch gate).  The estimate is
    dtype-aware: the storage policy's itemsize scales the
    state/prox-parameter blocks (``EdgeBlockLayout.window_bytes``), so
    bf16 widens the fusable window instead of falling back to the
    unfused path early.
    """
    pf = _param_floats(problem)
    itemsize = 4 if config is None else jnp.dtype(config.dtype).itemsize
    cap = fused_window_cap()
    nf = problem.num_features
    lt = _graph_layout(problem.graph, window_hint=(
        nf, pf or 0, itemsize, cap))
    window = (None if pf is None else
              lt.window_bytes(nf, param_floats=pf, itemsize=itemsize))
    return lt, window, cap


def _fused_window_fits(problem: Problem,
                       config: SolverConfig | None = None) -> bool:
    """Whether the planned layout's window fits the VMEM cap."""
    _, window, cap = _fused_window(problem, config)
    return window is not None and window <= cap


def _should_fuse(problem: Problem, config: SolverConfig) -> bool:
    """The one fused-dispatch gate, shared by solve_pallas and
    solve_path so the two can never route differently."""
    return (_fused_enabled(config) and _fused_supported(problem, config)
            and _fused_window_fits(problem, config))


def _fused_setup(graph, data, lam, w_true, layout_arrays, *, loss, reg,
                 layout, dtype: str = "float32"):
    """Shared per-solve prep for the fused scan/tol engines: layout
    padding, stepsizes, windowed prox parameters, and the metric fn.

    ``dtype`` is the storage policy: float prox-parameter stores are
    cast to it (bf16 halves their HBM<->VMEM traffic) while the
    step/index tensors (tau, sigma, src/dst, la) stay f32 and the
    metric fn always evaluates in f32.
    """
    lt = layout
    (node_perm, node_inv, src_l, dst_l, weights_l, edge_pos) = layout_arrays
    store_dt = jnp.dtype(dtype)

    # the paper-eq.-13 stepsizes come from the one source of truth
    # (EmpiricalGraph), gathered into layout order (pad nodes: tau 1)
    tau_l = gather_padded(graph.primal_stepsizes(), node_perm, fill=1.0)
    sig_l = jnp.full((lt.edges_pad,), 0.5, jnp.float32)
    sig_l = sig_l.at[edge_pos].set(graph.dual_stepsizes())

    def gather_nodes(a):
        return gather_padded(a, node_perm)

    data_l = NodeData(x=gather_nodes(data.x), y=gather_nodes(data.y),
                      sample_mask=gather_nodes(data.sample_mask),
                      labeled_mask=gather_nodes(data.labeled_mask))
    params = loss.prox_setup(data_l, tau_l)
    pkeys = tuple(sorted(params))
    params_s = tuple(
        lt.pad_node_store(params[k]).astype(store_dt)
        if jnp.issubdtype(params[k].dtype, jnp.floating)
        else lt.pad_node_store(params[k])
        for k in pkeys)
    tau_s = lt.pad_node_store(tau_l[:, None])
    ends = edge_ends_store(src_l, dst_l, lt.klo, lt.khi, lt.block_edges)
    sig2 = sig_l[:, None]
    la2 = (lam * weights_l)[:, None]
    unlabeled = 1.0 - data.labeled_mask

    def metrics(w_l):
        w = jnp.take(w_l, node_inv, axis=0).astype(jnp.float32)
        obj = loss.empirical_error(data, w) + reg.value(graph, w, lam)
        if w_true is None:
            mse = jnp.float32(0.0)
        else:
            mse = graph_signal_mse(w, w_true, unlabeled)
        return obj, mse

    return (params_s, pkeys, tau_l, tau_s, sig_l, sig2, ends, la2, metrics)


def _fused_run_iters(lt, ends, params_s, pkeys, tau_s, sig2, la2, *, loss,
                     reg, rho, use_kernel, compute_residual: bool = False):
    """Build ``run_iters(state, iters)`` advancing the padded stores.

    The scan carries the *padded* stores: the halo padding rows are
    never written, so writing each step's owned output back with a
    dynamic_update_slice (in-place under XLA's loop aliasing) avoids
    re-materializing the padded tensors every iteration.

    With ``compute_residual`` each call also returns the f32 eq.-11
    residual scalar the kernel accumulated in-kernel (max over blocks
    and, for ``iters > 1``, over iterations):
    ``run_iters(state, iters) -> (state, residual)``.
    """
    bv, eb = lt.block_nodes, lt.block_edges
    kn, klo, khi = lt.kn, lt.klo, lt.khi

    def run_iters(state, iters):
        w_store, u_store = state
        out = ops.pd_step(
            w_store, u_store, ends, params_s, tau_s, sig2, la2, loss=loss,
            reg=reg, pkeys=pkeys, block_nodes=bv,
            block_edges=eb, kn=kn, klo=klo, khi=khi, rho=rho, iters=iters,
            compute_residual=compute_residual, use_kernel=use_kernel)
        if compute_residual:
            w_new, u_new, res = out
        else:
            w_new, u_new = out
        new = (jax.lax.dynamic_update_slice(w_store, w_new, (0, 0)),
               jax.lax.dynamic_update_slice(u_store, u_new,
                                            (klo * eb, 0)))
        return (new, res) if compute_residual else new

    return run_iters


def _fused_scan_impl(graph, data, w0_l, u0_l, lam, w_true, layout_arrays,
                     *, loss: Loss, reg: Regularizer,
                     layout, num_iters: int, rho: float, metric_every: int,
                     use_kernel: bool, record_residual: bool = False,
                     dtype: str = "float32"):
    """Jitted fused engine: scan the fused PD step over the edge-blocked
    layout, recording metrics (in original node order, exactly the dense
    engine's formulas) on the cadence.

    ``layout`` is static (block extents); the layout's arrays come in as
    the traced ``layout_arrays`` tuple so they stay
    device buffers rather than jaxpr constants.  ``dtype`` is the
    storage policy for the scanned state and prox parameters (bf16
    halves the window traffic; accumulation stays f32 — see
    ``kernels.ref.pd_window_step``); returned ``w``/``u`` and all
    traces are f32 regardless.
    """
    lt = layout
    store_dt = jnp.dtype(dtype)
    w0_l, u0_l = w0_l.astype(store_dt), u0_l.astype(store_dt)
    (params_s, pkeys, tau_l, tau_s, sig_l, sig2, ends, la2,
     metrics) = _fused_setup(graph, data, lam, w_true, layout_arrays,
                             loss=loss, reg=reg, layout=lt, dtype=dtype)

    run_iters = _fused_run_iters(
        lt, ends, params_s, pkeys, tau_s, sig2, la2, loss=loss, reg=reg,
        rho=rho, use_kernel=use_kernel)

    eb, klo, khi = lt.block_edges, lt.klo, lt.khi

    def owned(state):
        w_store, u_store = state
        return (jax.lax.slice_in_dim(w_store, 0, lt.nodes_pad),
                jax.lax.slice_in_dim(u_store, klo * eb,
                                     klo * eb + lt.edges_pad))

    residual_fn = None
    if record_residual:
        def residual_fn(prev, new):
            w_p, u_p = owned(prev)
            w_n, u_n = owned(new)
            # f32 accumulation regardless of the storage policy
            return pd_residual(tau_l, sig_l, w_p.astype(jnp.float32),
                               u_p.astype(jnp.float32),
                               w_n.astype(jnp.float32),
                               u_n.astype(jnp.float32))

    w_store0 = lt.pad_node_store(w0_l)
    u_store0 = jnp.pad(u0_l, ((klo * eb, khi * eb), (0, 0)))
    (w_store, u_store), traces = scan_solve(
        run_iters, lambda s: metrics(s[0]), (w_store0, u_store0),
        num_iters=num_iters, metric_every=metric_every,
        multi_iter_block=(lt.num_blocks == 1), residual_fn=residual_fn)
    if record_residual:
        (obj_trace, mse_trace), res_trace = traces
    else:
        (obj_trace, mse_trace), res_trace = traces, None
    w_l, u_l = owned((w_store, u_store))
    return (w_l.astype(jnp.float32), u_l.astype(jnp.float32), obj_trace,
            mse_trace, res_trace)


_fused_scan = _jit(_fused_scan_impl,
                   static_argnames=("loss", "reg", "layout", "num_iters",
                                    "rho", "metric_every", "use_kernel",
                                    "record_residual", "dtype"),
                   donate_argnums=(2, 3))


def _fused_tol_impl(graph, data, w_store0, u_store0, lam, w_true,
                    node_inv, ends, params_s, tau_s, sig2, la2, tol, *,
                    loss: Loss, reg: Regularizer,
                    layout, pkeys, num_iters: int, rho: float,
                    metric_every: int, use_kernel: bool):
    """Device-resident fused tol engine: the ``lax.while_loop`` driver
    over metric blocks with the eq.-11 residual computed *in-kernel*
    (``kernels/pd_step.py``) — the stopping signal is born on device and
    never leaves it; the caller's single fetch of the iteration count is
    the solve's one device->host transfer.

    All per-solve setup (layout gathers, prox parameters, padded
    stepsizes) is precomputed once by the caller and arrives as traced
    operands.  When the whole graph is one VMEM block, each metric
    block is a *single* kernel launch (``iters=metric_every``) whose
    running-max residual rides the VMEM carry; otherwise the block
    scans single launches, each returning its per-launch residual max.
    """
    lt = layout
    run_iters = _fused_run_iters(
        lt, ends, params_s, pkeys, tau_s, sig2, la2, loss=loss, reg=reg,
        rho=rho, use_kernel=use_kernel, compute_residual=True)

    eb, klo = lt.block_edges, lt.klo
    metrics = make_metrics_fn(loss, reg, graph, data, lam, w_true)

    def block_metrics(w_store):
        w_l = jax.lax.slice_in_dim(w_store, 0, lt.nodes_pad)
        w = jnp.take(w_l, node_inv, axis=0).astype(jnp.float32)
        return metrics(w)

    if lt.num_blocks == 1:
        def run_block(state):
            state, res = run_iters(state, metric_every)
            obj, mse = block_metrics(state[0])
            return state, (obj, mse, res), res
    else:
        def run_block(state):
            def step(st, _):
                return run_iters(st, 1)
            state, res = jax.lax.scan(step, state, None,
                                      length=metric_every)
            res = jnp.max(res)
            obj, mse = block_metrics(state[0])
            return state, (obj, mse, res), res

    (w_store, u_store), (obj, mse, res), its = device_loop(
        run_block, (w_store0, u_store0), num_iters=num_iters,
        metric_every=metric_every, tol=tol)
    return w_store, u_store, obj, mse, res, its


_fused_tol = _jit(_fused_tol_impl,
                  static_argnames=("loss", "reg", "layout", "pkeys",
                                   "num_iters", "rho", "metric_every",
                                   "use_kernel"),
                  donate_argnums=(2, 3))


def _solve_fused(problem: Problem, config: SolverConfig, *, w0=None,
                 u0=None, w_true=None) -> SolveResult:
    """Solve via the fused PD kernel on the edge-blocked graph layout.

    Its spans: ``solver_setup`` (layout gathers, ``_fused_setup``, the
    padded stores), ``solver_engine`` and ``solver_unpack`` (the trace
    buffers cut to the stopping block, ``w`` and ``u`` un-permuted).
    On the tol path ``solver_engine`` runs through the fetch of the
    stopping iteration, a sync, so the device time of the set-up and of
    the loop lands inside it; on the fixed-iteration path it holds the
    scan's dispatch only.
    """
    _check_cadence(config)
    dtype = _storage_dtype(config, fused=True)
    store_dt = jnp.dtype(dtype)
    lt = _graph_layout(problem.graph)
    n = problem.num_features
    data = problem.data
    # 0-iteration budget: degenerate 0-length scan, no while loop
    fixed = config.tol is None or config.num_iters == 0
    layout_arrays = (lt.node_perm, lt.node_inv, lt.src, lt.dst, lt.weights,
                     lt.edge_pos)
    use_kernel = ops._use_kernel_default()

    with obs.span("solver_setup"):
        if w0 is None:
            w0_l = jnp.zeros((lt.nodes_pad, n), jnp.float32)
        else:
            w0_l = gather_padded(jnp.asarray(w0, jnp.float32), lt.node_perm)
        u0_l = jnp.zeros((lt.edges_pad, n), jnp.float32)
        if u0 is not None:
            u0_l = u0_l.at[lt.edge_pos].set(
                jnp.asarray(u0, jnp.float32) * lt.edge_flip[:, None])
        if not fixed:
            # per-solve setup (layout gathers, prox params, padded
            # stepsizes) runs once, eagerly; the while loop advances the
            # padded stores in the storage dtype
            (params_s, pkeys, tau_l, tau_s, sig_l, sig2, ends, la2,
             _metrics) = _fused_setup(
                problem.graph, data, problem.lam, w_true, layout_arrays,
                loss=problem.loss, reg=problem.regularizer, layout=lt,
                dtype=dtype)
            eb, klo = lt.block_edges, lt.klo
            store0 = (lt.pad_node_store(w0_l).astype(store_dt),
                      jnp.pad(u0_l, ((klo * eb, lt.khi * eb),
                                     (0, 0))).astype(store_dt))
    with obs.span("solver_engine"):
        if fixed:
            w_l, u_l, obj, mse, res = _fused_scan(
                problem.graph, data, w0_l, u0_l, problem.lam, w_true,
                layout_arrays, loss=problem.loss,
                reg=problem.regularizer, layout=lt,
                num_iters=config.num_iters, rho=config.rho,
                metric_every=config.metric_every, use_kernel=use_kernel,
                record_residual=config.record_residual, dtype=dtype)
            iterations = config.num_iters
        else:
            w_store, u_store, obj, mse, res, its = _fused_tol(
                problem.graph, data, store0[0], store0[1], problem.lam,
                w_true, lt.node_inv, ends, params_s, tau_s, sig2, la2,
                config.tol, loss=problem.loss,
                reg=problem.regularizer, layout=lt, pkeys=pkeys,
                num_iters=config.num_iters, rho=config.rho,
                metric_every=config.metric_every, use_kernel=use_kernel)
            # the solve's single device->host transfer: the stopping
            # iteration; the trace buffers truncate lazily from it
            (iterations,) = device_fetch((its,))
            iterations = int(iterations)
    with obs.span("solver_unpack"):
        if not fixed:
            nb = iterations // config.metric_every
            obj, mse, res = obj[:nb], mse[:nb], res[:nb]
            w_l = jax.lax.slice_in_dim(w_store, 0, lt.nodes_pad)
            u_l = jax.lax.slice_in_dim(u_store, klo * eb,
                                       klo * eb + lt.edges_pad)
        w = jnp.take(w_l, lt.node_inv, axis=0).astype(jnp.float32)
        u = (jnp.take(u_l, lt.edge_pos, axis=0)
             * lt.edge_flip[:, None]).astype(jnp.float32)
    diag = _with_iterations(_diagnostics(problem, w, u, config), config,
                            iterations)
    return SolveResult(w=w, u=u, objective=obj,
                       mse=None if w_true is None else mse,
                       lam=problem.lam, diagnostics=diag, residual=res)


@register_backend("pallas")
def solve_pallas(problem: Problem, config: SolverConfig, *, w0=None,
                 u0=None, w_true=None) -> SolveResult:
    """TPU-kernel backend.

    Default on TPU (opt-out via ``fused=False`` / ``REPRO_FUSED=0``): the
    *fused* primal-dual kernel — one VMEM-resident pass per iteration over
    the edge-blocked graph layout (``kernels/pd_step.py``), available for
    every registered loss (squared/lasso/logistic) and every fusable
    regularizer (``tv``/``tv2``).  Otherwise the dense path with the
    unfused TPU kernels auto-wired: the dual clip through
    ``kernels.ops.tv_prox`` (TV regularizer only) and the squared loss's
    affine prox through ``kernels.ops.batched_affine``;
    ``config.clip_fn``/``config.affine_fn`` override either (and disable
    fusion).

    Which of the two ran is recorded in ``diagnostics["route"]``:
    ``fused``, and for a planned layout its block extents, the VMEM
    window estimate and the cap it was held to — a window over the cap
    takes the unfused kernels, and the route says so.
    """
    fused = _should_fuse(problem, config)
    if fused:
        res = _solve_fused(problem, config, w0=w0, u0=u0, w_true=w_true)
    else:
        clip_fn, affine_fn = resolve_kernel_hooks(problem, config, True)
        res = _solve_dense(problem, config, w0=w0, u0=u0, w_true=w_true,
                           clip_fn=clip_fn, affine_fn=affine_fn)
    route = {"fused": fused}
    if fused or (_fused_enabled(config)
                 and _fused_supported(problem, config)):
        lt, window, cap = _fused_window(problem, config)
        route.update(block_nodes=lt.block_nodes, num_blocks=lt.num_blocks,
                     kn=lt.kn, klo=lt.klo, khi=lt.khi, window_bytes=window,
                     window_cap=cap)
    return dataclasses.replace(res, diagnostics={**res.diagnostics,
                                                 "route": route})


# ---------------------------------------------------------------------------
# Federated backend (round-based message-passing runtime, repro.federated)
# ---------------------------------------------------------------------------

@register_backend("federated")
def solve_federated(problem: Problem, config: SolverConfig, *, w0=None,
                    u0=None, w_true=None) -> SolveResult:
    """Run the federated message-passing runtime as a solver backend.

    ``config.federated`` (a ``repro.federated.FederatedConfig``) carries
    the runtime policies — participation, local updates, compression,
    checkpointing; this solver config's ``num_iters`` (as rounds),
    ``rho``, ``metric_every``, ``tol``, and ``compute_diagnostics``
    override the loop shape so backends stay comparable under one
    SolverConfig.  The default (``federated=None``) is synchronous full
    participation — the dense oracle mode the conformance suite locks
    down.
    """
    _storage_dtype(config, fused=False)
    # local import: repro.federated layers on this module (lazy both ways)
    import dataclasses as _dc

    from repro.federated import FederatedConfig, run_federated

    fed = (config.federated if config.federated is not None
           else FederatedConfig())
    if not isinstance(fed, FederatedConfig):
        raise TypeError("SolverConfig.federated must be a "
                        f"repro.federated.FederatedConfig, got {fed!r}")
    fed = _dc.replace(fed, num_rounds=config.num_iters, rho=config.rho,
                      metric_every=config.metric_every, tol=config.tol,
                      compute_diagnostics=config.compute_diagnostics)
    return run_federated(problem, fed, w0=w0, u0=u0,
                         w_true=w_true).to_solve_result()


# ---------------------------------------------------------------------------
# Sharded backend (shard_map message passing, core/distributed.py)
# ---------------------------------------------------------------------------

@register_backend("sharded")
def solve_sharded(problem: Problem, config: SolverConfig, *, w0=None,
                  u0=None, w_true=None) -> SolveResult:
    """Partition the graph over ``config.mesh`` and run the halo-exchange
    solver.  Objective/MSE are evaluated once at the final iterate (the
    sharded loop carries prox parameters, not raw node data), so the traces
    have length 1.
    """
    _storage_dtype(config, fused=False)
    # local imports: core.distributed is a peer of the api package and
    # delegates its own front-end back here (lazy on both sides).
    from repro.core.distributed import (halo_exchange_bytes_per_iter,
                                        resolve_comm, shard_problem,
                                        solve_nlasso_sharded)
    from repro.core.partition import (permute_edge_array_device,
                                      permute_node_array_device,
                                      unpermute_edge_array_device,
                                      unpermute_node_array_device)
    from repro.core.mesh import make_device_mesh

    if not problem.regularizer.fusable:
        raise NotImplementedError(
            "sharded backend needs an edge-elementwise (fusable) "
            "regularizer resolvent")

    mesh = config.mesh if config.mesh is not None else make_device_mesh()
    num_shards = (config.num_shards if config.num_shards is not None
                  else mesh.shape[config.mesh_axis])
    sp = shard_problem(problem.graph, problem.data, num_shards,
                       partitioner=config.partitioner, loss=problem.loss)
    comm = resolve_comm(
        config.comm,
        sp.plan.cut_edges / max(problem.graph.num_edges, 1))
    # device-side layout permutes (jnp gathers): warm-started continuation
    # sweeps keep the carry on device instead of bouncing through numpy
    if w0 is not None:
        w0 = permute_node_array_device(sp.plan, w0)
    if u0 is not None:
        u0 = permute_edge_array_device(sp.plan, u0)
    lam = float(problem.lam)
    w_pad, u_pad, iterations = solve_nlasso_sharded(
        sp, mesh, lam, config.num_iters, axis=config.mesh_axis,
        rho=config.rho, comm=comm, w0=w0, u0=u0, return_u=True,
        tol=config.tol, tol_every=config.metric_every,
        reg=problem.regularizer)
    w = unpermute_node_array_device(sp.plan, w_pad, problem.graph.num_nodes)
    u = unpermute_edge_array_device(sp.plan, u_pad, problem.graph.num_edges)
    obj = problem.objective(w)[None]
    if w_true is None:
        mse = None
    else:
        mse = graph_signal_mse(w, w_true,
                               1.0 - problem.data.labeled_mask)[None]
    diag = _with_iterations(_diagnostics(problem, w, u, config), config,
                            iterations)
    diag = _with_halo_traffic(
        diag, halo_exchange_bytes_per_iter(sp, comm, problem.num_features),
        iterations, comm, "sharded")
    return SolveResult(w=w, u=u, objective=obj, mse=mse, lam=problem.lam,
                       diagnostics=diag)


def _with_halo_traffic(diag, bytes_per_iter: int, iterations: int,
                       comm: str, backend: str):
    """Surface inter-shard exchange volume per solve (and mirror it onto
    the obs registry, CommLedger.export_obs-style)."""
    total = int(bytes_per_iter) * int(iterations)
    diag = dict(diag or {})
    diag["halo_exchange_bytes_per_iter"] = float(bytes_per_iter)
    diag["halo_exchange_bytes"] = float(total)
    if obs.enabled():
        obs.counter(
            "halo_exchange_bytes_total",
            help="inter-shard dual/primal halo exchange payload bytes",
            comm=comm, backend=backend).inc(total)
        obs.counter(
            "halo_exchange_iterations_total",
            help="iterations contributing halo exchanges",
            comm=comm, backend=backend).inc(int(iterations))
    return diag


@register_backend("sharded_fused")
def solve_sharded_fused(problem: Problem, config: SolverConfig, *, w0=None,
                        u0=None, w_true=None) -> SolveResult:
    """Two-level scale-out: hierarchical partition (cluster cuts between
    shards, RCM + edge blocks within), each shard_map shard stepping the
    fused edge-blocked kernel with a per-iteration dual halo refresh
    between shards.  ``comm="auto"`` (the default) picks the boundary
    exchange when the inter-shard cut fraction is < 25%.  Objective/MSE
    are evaluated once at the final iterate, like ``sharded``.
    """
    _storage_dtype(config, fused=False)
    from repro.core.distributed import (halo_exchange_bytes_per_iter,
                                        resolve_comm, shard_problem_fused,
                                        solve_nlasso_hier)
    from repro.core.mesh import make_device_mesh

    if not problem.regularizer.fusable:
        raise NotImplementedError(
            "sharded_fused needs an edge-elementwise (fusable) "
            "regularizer resolvent")
    if ops._use_kernel_default() and not problem.loss.kernel_safe:
        raise NotImplementedError(
            f"loss {type(problem.loss).__name__} cannot lower inside the "
            "Pallas kernel; run sharded_fused off-TPU or use sharded")
    if config.clip_fn is not None or config.affine_fn is not None:
        raise NotImplementedError(
            "custom kernel hooks target the unfused engine")

    mesh = config.mesh if config.mesh is not None else make_device_mesh()
    num_shards = (config.num_shards if config.num_shards is not None
                  else mesh.shape[config.mesh_axis])
    pf = _param_floats(problem) or 0
    cap = fused_window_cap()
    sp = shard_problem_fused(problem.graph, problem.data, num_shards,
                             partitioner=config.partitioner,
                             loss=problem.loss,
                             window_hint=(problem.num_features, pf, 4, cap))
    h = sp.hier
    window = fused_window_bytes(h.block_nodes, h.block_edges, h.kn, h.klo,
                                h.khi, problem.num_features,
                                param_floats=pf)
    if window > cap:
        # never hand the chip's compiler a kernel it would refuse
        raise ValueError(
            f"sharded_fused ({num_shards} shards, {h.num_blocks} blocks "
            f"per shard): the fused window needs {window} bytes of VMEM "
            f"(block_nodes={h.block_nodes}, block_edges={h.block_edges}, "
            f"kn={h.kn}, klo={h.klo}, khi={h.khi}) but the cap is {cap} "
            "bytes; use more shards or backend='sharded'")
    lam = float(problem.lam)
    w_np, u_np, iterations, comm = solve_nlasso_hier(
        sp, mesh, lam, config.num_iters, axis=config.mesh_axis,
        rho=config.rho, comm=resolve_comm(config.comm, sp.hier.cut_fraction),
        w0=None if w0 is None else np.asarray(w0),
        u0=None if u0 is None else np.asarray(u0),
        tol=config.tol, tol_every=config.metric_every,
        reg=problem.regularizer)
    w, u = jnp.asarray(w_np), jnp.asarray(u_np)
    obj = problem.objective(w)[None]
    if w_true is None:
        mse = None
    else:
        mse = graph_signal_mse(w, w_true,
                               1.0 - problem.data.labeled_mask)[None]
    diag = _with_iterations(_diagnostics(problem, w, u, config), config,
                            iterations)
    diag = _with_halo_traffic(
        diag, halo_exchange_bytes_per_iter(sp, comm, problem.num_features),
        iterations, comm, "sharded_fused")
    return SolveResult(w=w, u=u, objective=obj, mse=mse, lam=problem.lam,
                       diagnostics=diag)
