"""Distributed (sharded) nLasso solver — explicit shard_map message passing.

This is the federated/distributed realization of Algorithm 1: the empirical
graph is partitioned over the ``data`` axis of a device mesh; each shard
owns a contiguous slice of nodes (primal state + local datasets + prox
parameters) and the edges whose ``src`` endpoint it owns (dual state).

The iteration body is the canonical engine step
(:func:`repro.engine.step.pd_step`) evaluated through a
:class:`repro.engine.executors.HaloExecutor`, whose per-iteration
communication pattern is (DESIGN.md §3.3):

  * ``dense`` mode (baseline): one ``all_gather`` of the primal block
    (V_pad x n) to evaluate D w, and one ``psum`` of the dense D^T u
    accumulator (V_pad x n).  Total per-iteration collective volume
    2 * V_pad * n * 4 bytes per device — independent of the partition.
  * ``boundary`` mode (beyond-paper optimization, see EXPERIMENTS.md §Perf):
    only rows that participate in cut edges are exchanged; volume
    2 * B * n * 4 with B = padded boundary size.  With a cluster-aware
    partition (core/partition.py) B << V.

The TPU adaptation note: the paper's per-edge messages become regular
lock-step collectives — the ICI-idiomatic equivalent of gossip on a graph.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import losses as L
from repro.core.graph import EmpiricalGraph, edge_ends_store
from repro.core.partition import (HierarchyPlan, PartitionPlan,
                                  block_partition, cluster_partition,
                                  plan_hierarchy, plan_partition,
                                  permute_node_array)
from repro.engine import HaloExecutor, pd_residual, run_chunked
from repro.engine import pd_step as engine_pd_step


@dataclasses.dataclass(frozen=True)
class ShardedProblem:
    """Device-layout view of (graph, data) according to a PartitionPlan."""
    plan: PartitionPlan
    # node-sharded (S*vp, ...) arrays
    tau: jnp.ndarray
    prox_params: dict
    # edge-sharded (S*ep, ...) arrays
    src: jnp.ndarray
    dst: jnp.ndarray
    bound_unit: jnp.ndarray      # A_e (0 for padded edges)
    # boundary-exchange metadata
    send_rows: jnp.ndarray       # (S*vp,) 1.0 if node participates in a cut edge
    loss: object = None          # Loss instance (defaults to SquaredLoss)
    num_features: int = 0


def _resolve_loss(loss):
    """Accept a Loss instance or a legacy registry name; reject losses
    without a kernelizable ``prox_setup`` (the sharded loop carries prox
    parameters, not the loss closure)."""
    from repro.api.losses import Loss, get_loss

    obj = get_loss(loss) if isinstance(loss, str) else loss
    if type(obj).prox_setup is Loss.prox_setup:
        raise NotImplementedError(
            f"loss {type(obj).__name__} has no prox_setup parameterization;"
            " the sharded backends need one (use the dense/pallas backends)")
    return obj


def _permute_data(plan_or_hier, data: L.NodeData, perm_fn) -> L.NodeData:
    """Reorder node datasets into a device layout, zero-filling padding.

    Zero-filled rows are exactly the 'no samples, unlabeled' node: every
    stock ``Loss.prox_setup`` maps them to the identity prox (``counts``
    is zero-safe), so permuting the *data* and running ``prox_setup`` in
    layout order supports arbitrary param pytrees — per-node prox setup
    commutes with node permutation.
    """
    return L.NodeData(
        x=jnp.asarray(perm_fn(plan_or_hier, np.asarray(data.x), 0.0)),
        y=jnp.asarray(perm_fn(plan_or_hier, np.asarray(data.y), 0.0)),
        sample_mask=jnp.asarray(
            perm_fn(plan_or_hier, np.asarray(data.sample_mask), 0.0)),
        labeled_mask=jnp.asarray(
            perm_fn(plan_or_hier, np.asarray(data.labeled_mask), 0.0)),
    )


def shard_problem(graph: EmpiricalGraph, data: L.NodeData,
                  num_shards: int, *, partitioner: str = "cluster",
                  loss="squared", seed: int = 0) -> ShardedProblem:
    """Partition the graph + data and precompute shard-layout prox params.

    Works for any :class:`repro.api.losses.Loss` with a ``prox_setup``
    parameterization (squared / lasso / logistic): the node datasets are
    permuted into plan layout (zero fill → identity prox on padding) and
    ``prox_setup`` runs there, so arbitrary param pytrees come out
    already sharded.
    """
    loss_obj = _resolve_loss(loss)

    if partitioner == "cluster":
        assign = cluster_partition(graph, num_shards, seed=seed)
    elif partitioner == "block":
        assign = block_partition(graph.num_nodes, num_shards)
    else:
        raise ValueError(partitioner)
    plan = plan_partition(graph, assign, num_shards)

    tau_full = np.asarray(graph.primal_stepsizes())
    tau = permute_node_array(plan, tau_full, fill=1.0)

    data_pad = _permute_data(plan, data, permute_node_array)
    params = loss_obj.prox_setup(data_pad,
                                 jnp.asarray(tau.astype(np.float32)))

    # boundary rows: nodes touching a cut edge (new numbering)
    src_old = np.asarray(graph.src)
    dst_old = np.asarray(graph.dst)
    cut = assign[src_old] != assign[dst_old]
    send = np.zeros(len(plan.node_perm), np.float32)
    bn = np.unique(np.concatenate([src_old[cut], dst_old[cut]]))
    send[plan.node_inv[bn]] = 1.0

    return ShardedProblem(
        plan=plan,
        tau=jnp.asarray(tau.astype(np.float32)),
        prox_params={k: jnp.asarray(v) for k, v in params.items()},
        src=jnp.asarray(plan.src_new, jnp.int32),
        dst=jnp.asarray(plan.dst_new, jnp.int32),
        bound_unit=jnp.asarray(plan.weights),
        send_rows=jnp.asarray(send),
        loss=loss_obj,
        num_features=int(data.num_features),
    )


def _make_sharded_run(problem: ShardedProblem, mesh: Mesh, lam: float,
                      *, axis: str, rho: float, comm: str,
                      num_iters: int, with_residual: bool, reg=None):
    """Build the shard_map program scanning ``num_iters`` engine steps.

    With ``with_residual`` the program additionally returns each shard's
    local max per-iteration fixed-point residual over the chunk (a (1,)
    row per shard; the host maxes over shards), which is what the tol
    chunk loop compares against the tolerance.
    """
    from repro.api.losses import SquaredLoss
    from repro.api.regularizers import TotalVariation

    plan = problem.plan
    S, vp = plan.num_shards, plan.nodes_per_shard
    V_pad = S * vp
    sigma = 0.5
    loss = problem.loss if problem.loss is not None else SquaredLoss()
    reg = reg if reg is not None else TotalVariation()
    pkeys = tuple(sorted(problem.prox_params))
    pleaves = tuple(problem.prox_params[k] for k in pkeys)
    # every prox_setup leaf is a (S*vp, ...) node array: shard axis 0
    pspecs = tuple(P(axis, *(None,) * (a.ndim - 1)) for a in pleaves)

    node_spec = P(axis)
    edge_spec = P(axis)
    out_specs = (node_spec, edge_spec)
    if with_residual:
        out_specs = out_specs + (edge_spec,)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(node_spec, edge_spec, node_spec,
                       edge_spec, edge_spec, edge_spec, node_spec) + pspecs,
             out_specs=out_specs)
    def run(w, u, tau, src, dst, wts, send, *pvals):
        me = jax.lax.axis_index(axis)
        send_full = jax.lax.all_gather(send, axis, tiled=True) \
            if comm == "boundary" else None
        executor = HaloExecutor(
            axis=axis, comm=comm, vp=vp, v_pad=V_pad, base=me * vp,
            src=src, dst=dst, weights=wts, send=send,
            send_full=send_full)
        params = dict(zip(pkeys, pvals))

        def prox(v):
            return loss.prox_apply(params, v)

        def body(state, _):
            w_loc, u_loc = state
            new = engine_pd_step(executor, prox, reg, lam, tau, sigma,
                                 w_loc, u_loc, rho=rho)
            if with_residual:
                return new, pd_residual(tau, sigma, w_loc, u_loc,
                                        new[0], new[1])
            return new, None

        (w_fin, u_fin), res = jax.lax.scan(body, (w, u), None,
                                           length=num_iters)
        if with_residual:
            # chunk-max residual, like every other backend's tol chunk
            return w_fin, u_fin, jnp.max(res)[None]
        return w_fin, u_fin

    return run


def solve_nlasso_sharded(problem: ShardedProblem, mesh: Mesh, lam: float,
                         num_iters: int, *, axis: str = "data",
                         rho: float = 1.0, comm: str = "dense",
                         w0: jnp.ndarray | None = None,
                         u0: jnp.ndarray | None = None,
                         return_u: bool = False,
                         tol: float | None = None,
                         tol_every: int | None = None,
                         reg=None):
    """Run Algorithm 1 under shard_map; returns W in plan layout (S*vp, n).

    ``comm``: "dense" | "boundary" (see module docstring).  ``w0``/``u0``
    warm-start the iteration (plan layout); ``return_u=True`` additionally
    returns the final dual state U in plan layout (S*ep, n) and the
    iteration count actually run.  ``tol`` enables residual-based early
    stopping: the horizon advances in ``tol_every``-iteration chunks and
    stops at the first chunk whose (shard-maxed) fixed-point residual is
    <= tol.
    """
    plan = problem.plan
    S, vp, ep = plan.num_shards, plan.nodes_per_shard, plan.edges_per_shard
    n = problem.num_features or problem.prox_params["b"].shape[1]
    V_pad = S * vp
    if w0 is None:
        w0 = jnp.zeros((V_pad, n), jnp.float32)
    if u0 is None:
        u0 = jnp.zeros((S * ep, n), jnp.float32)
    pleaves = tuple(problem.prox_params[k]
                    for k in sorted(problem.prox_params))
    operands = (problem.tau, problem.src, problem.dst,
                problem.bound_unit, problem.send_rows) + pleaves

    if tol is None or num_iters == 0:
        run = _make_sharded_run(problem, mesh, lam, axis=axis, rho=rho,
                                comm=comm, num_iters=num_iters,
                                with_residual=False, reg=reg)
        w_out, u_out = run(w0, u0, *operands)
        iterations = num_iters
    else:
        # the shared chunk driver (engine.loop.run_chunked) owns the
        # stopping rule; this backend only supplies the chunk program
        chunk = int(tol_every) if tol_every else min(50, num_iters)
        runs = {}

        def run_chunk(state, r0, r1):
            length = r1 - r0
            if length not in runs:
                runs[length] = _make_sharded_run(
                    problem, mesh, lam, axis=axis, rho=rho, comm=comm,
                    num_iters=length, with_residual=True, reg=reg)
            w_, u_, res = runs[length](*state, *operands)
            # (S,) per-shard chunk-max residuals -> one host scalar
            return (w_, u_), (), np.max(np.asarray(res))

        (w_out, u_out), _traces, iterations, _ = run_chunked(
            run_chunk, (w0, u0), total=num_iters, chunk_size=chunk,
            tol=tol)

    return (w_out, u_out, iterations) if return_u else w_out


# ---------------------------------------------------------------------------
# Hierarchical (two-level) solver: fused edge-blocked kernel inside each
# shard_map shard, dual halo refresh between shards (ROADMAP scale-out).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HierarchicalProblem:
    """Device-layout view of (graph, data) under a :class:`HierarchyPlan`.

    Node-store arrays are stacked per shard at ``w_store_rows`` rows each
    (owned+halo layout rows plus the fused kernel's inert suffix
    padding); edge tables at ``edges_pad`` owned slots per shard, except
    ``ends``, which follows the dual store's ``u_store_rows``.
    """
    hier: HierarchyPlan
    loss: object
    num_features: int
    # node stores (S * WSR, ...)
    tau: jnp.ndarray
    prox_params: dict
    node_owned: jnp.ndarray      # (S * NV, 1)
    ends: jnp.ndarray            # (S * ESR, 2) (src, dst) per u-store row
    # owned edge slots (S * NE, 1)
    bound_unit: jnp.ndarray      # A_e (0 for padding/replica-free slots)
    edge_owned: jnp.ndarray
    orient: jnp.ndarray
    # dual-refresh exchange tables
    send_idx: jnp.ndarray        # (S * NS,)
    send_flip: jnp.ndarray       # (S * NS, 1)
    recv_src_boundary: jnp.ndarray   # (S * NE,)
    recv_src_dense: jnp.ndarray      # (S * NE,)
    recv_flip: jnp.ndarray           # (S * NE, 1)


def _hier_gather(idx: np.ndarray, arr: np.ndarray, fill) -> np.ndarray:
    """Row-gather ``arr[idx]`` with ``idx == -1`` rows set to ``fill``."""
    arr = np.asarray(arr)
    out = np.full(idx.shape + arr.shape[1:], fill, dtype=arr.dtype)
    valid = idx >= 0
    out[valid] = arr[idx[valid]]
    return out


def shard_problem_fused(graph: EmpiricalGraph, data: L.NodeData,
                        num_shards: int, *, partitioner: str = "cluster",
                        loss="squared", seed: int = 0,
                        window_hint: tuple | None = None,
                        assign: np.ndarray | None = None
                        ) -> HierarchicalProblem:
    """Two-level shard prep: cluster cuts between shards, an edge-blocked
    fused-kernel layout within each (``core.partition.plan_hierarchy``).

    Prox parameters come out already in stacked per-shard store order:
    the node datasets are gathered into each shard's layout (zero fill →
    identity prox on padding *and* a consistent copy on halo rows, whose
    primal updates are recomputed redundantly per shard) and
    ``loss.prox_setup`` runs on the stacked rows — per-node setup
    commutes with the gather, so any param pytree is supported.
    """
    loss_obj = _resolve_loss(loss)
    if assign is None:
        if partitioner == "cluster":
            assign = cluster_partition(graph, num_shards, seed=seed)
        elif partitioner == "block":
            assign = block_partition(graph.num_nodes, num_shards)
        else:
            raise ValueError(partitioner)
    hier = plan_hierarchy(graph, assign, num_shards,
                          window_hint=window_hint)
    S = hier.num_shards
    NE = hier.edges_pad

    tau_full = np.asarray(graph.primal_stepsizes(), np.float32)
    tau = _hier_gather(hier.w_inj, tau_full, 1.0)[:, None]

    def perm_fn(_, arr, fill):
        return _hier_gather(hier.w_inj, arr, fill)

    data_store = _permute_data(hier, data, perm_fn)
    params = loss_obj.prox_setup(data_store, jnp.asarray(tau[:, 0]))

    return HierarchicalProblem(
        hier=hier, loss=loss_obj, num_features=int(data.num_features),
        tau=jnp.asarray(tau),
        prox_params={k: jnp.asarray(v) for k, v in params.items()},
        node_owned=jnp.asarray(hier.node_owned[:, None]),
        ends=jnp.concatenate([
            edge_ends_store(hier.src[sl], hier.dst[sl], hier.klo,
                            hier.khi, hier.block_edges)
            for sl in (slice(s * NE, (s + 1) * NE) for s in range(S))]),
        bound_unit=jnp.asarray(hier.weights[:, None]),
        edge_owned=jnp.asarray(hier.edge_owned[:, None]),
        orient=jnp.asarray(hier.orient[:, None]),
        send_idx=jnp.asarray(hier.send_idx, jnp.int32),
        send_flip=jnp.asarray(hier.send_flip[:, None]),
        recv_src_boundary=jnp.asarray(hier.recv_src, jnp.int32),
        recv_src_dense=jnp.asarray(hier.recv_src_dense, jnp.int32),
        recv_flip=jnp.asarray(hier.recv_flip[:, None]),
    )


def resolve_comm(comm: str, cut_fraction: float,
                 threshold: float = 0.25) -> str:
    """``auto`` → boundary when the inter-shard cut is small (the
    compacted exchange then moves far fewer rows than the owned slab)."""
    if comm == "auto":
        return "boundary" if cut_fraction < threshold else "dense"
    return comm


def halo_exchange_bytes_per_iter(problem, comm: str, num_features: int,
                                 itemsize: int = 4) -> int:
    """Per-iteration bytes *published* across the mesh (all shards).

    Mirrors ``federated.CommLedger``'s accounting convention (payload
    bytes entering the collective, not link-level traffic).  Accepts
    either a :class:`ShardedProblem` (HaloExecutor: primal all-gather +
    dense/boundary D^T u reduction → 2 blocks per device) or a
    :class:`HierarchicalProblem` (one owned-dual refresh per iteration).
    """
    n = num_features
    if isinstance(problem, HierarchicalProblem):
        h = problem.hier
        return h.num_shards * h.exchange_rows(comm) * n * itemsize
    plan = problem.plan
    S, vp = plan.num_shards, plan.nodes_per_shard
    if comm == "boundary":
        rows = int(np.asarray(problem.send_rows).sum())
    else:
        rows = S * vp
    return S * 2 * rows * n * itemsize


def _make_hier_run(problem: HierarchicalProblem, mesh: Mesh, lam: float,
                   *, axis: str, rho: float, comm: str, num_iters: int,
                   with_residual: bool, reg=None):
    """Build the shard_map program: per shard, per iteration, one dual
    halo refresh (``HierarchicalExecutor.refresh_duals``) then one fused
    edge-blocked kernel step (``kernels.ops.pd_step``) over the shard's
    local layout.  Owned rows evolve exactly as the global iteration
    (the local subgraph is the 1-hop halo closure), so the per-shard
    residual rows max to the global eq.-11 residual on the host.
    """
    from repro.api.regularizers import TotalVariation
    from repro.engine import HierarchicalExecutor
    from repro.kernels import ops

    h = problem.hier
    loss = problem.loss
    reg = reg if reg is not None else TotalVariation()
    BV, EB = h.block_nodes, h.block_edges
    nb, kn, klo, khi = h.num_blocks, h.kn, h.klo, h.khi
    NE = h.edges_pad
    pkeys = tuple(sorted(problem.prox_params))
    pleaves = tuple(problem.prox_params[k] for k in pkeys)
    pspecs = tuple(P(axis, *(None,) * (a.ndim - 1)) for a in pleaves)
    recv_src = (problem.recv_src_boundary if comm == "boundary"
                else problem.recv_src_dense)

    sharded = lambda a: P(axis, *(None,) * (a.ndim - 1))  # noqa: E731
    fixed = (problem.tau, problem.node_owned, problem.ends,
             problem.bound_unit, problem.edge_owned, problem.orient,
             problem.send_idx, problem.send_flip, recv_src,
             problem.recv_flip)
    in_specs = ((P(axis, None), P(axis, None))
                + tuple(sharded(a) for a in fixed) + pspecs)
    out_specs = (P(axis, None), P(axis, None))
    if with_residual:
        out_specs = out_specs + (P(axis),)

    # check_vma=False: the fused kernel's pallas_call declares its
    # outputs without per-axis variance, which the checker requires
    @partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
             out_specs=out_specs, check_vma=False)
    def run(w_store, u_store, tau, n_own, ends, wts, e_own, orient,
            send_idx, send_flip, rsrc, rflip, *pvals):
        executor = HierarchicalExecutor(
            axis=axis, comm=comm, num_blocks=nb, block_nodes=BV,
            block_edges=EB, klo=klo, node_owned=n_own, edge_owned=e_own,
            orient=orient, send_idx=send_idx, send_flip=send_flip,
            recv_src=rsrc, recv_flip=rflip)
        sig = jnp.full((NE, 1), 0.5, jnp.float32)
        la = lam * wts

        def body(state, _):
            w_s, u_s = state
            u_r = executor.refresh_duals(u_s)
            w_new, u_new = ops.pd_step(
                w_s, u_r, ends, pvals, tau, sig, la, loss=loss, reg=reg,
                pkeys=pkeys, block_nodes=BV, block_edges=EB, kn=kn,
                klo=klo, khi=khi, rho=rho, iters=1, compute_residual=False)
            res = None
            if with_residual:
                res = executor.residual(w_s, u_r, w_new, u_new, tau, sig)
            return executor.write_back(w_s, u_r, w_new, u_new), res

        (w_fin, u_fin), res = jax.lax.scan(body, (w_store, u_store), None,
                                           length=num_iters)
        if with_residual:
            return w_fin, u_fin, jnp.max(res)[None]
        return w_fin, u_fin

    return run


def solve_nlasso_hier(problem: HierarchicalProblem, mesh: Mesh, lam: float,
                      num_iters: int, *, axis: str = "data",
                      rho: float = 1.0, comm: str = "auto",
                      w0: np.ndarray | None = None,
                      u0: np.ndarray | None = None,
                      tol: float | None = None,
                      tol_every: int | None = None, reg=None):
    """Run Algorithm 1 through the two-level executor composition.

    ``w0`` / ``u0`` warm-start in *original* (global) order; the returned
    ``(w, u, iterations)`` are in original order too — the hierarchy's
    injection/extraction gathers handle the stacked store layout, so
    callers never see it.  ``comm="auto"`` picks the boundary exchange
    when the inter-shard cut fraction is below 25%.
    """
    h = problem.hier
    n = problem.num_features
    comm = resolve_comm(comm, h.cut_fraction)
    S, WSR, ESR = h.num_shards, h.w_store_rows, h.u_store_rows

    w_st = np.zeros((S * WSR, n), np.float32)
    u_st = np.zeros((S * ESR, n), np.float32)
    if w0 is not None:
        w_st = _hier_gather(h.w_inj, np.asarray(w0, np.float32), 0.0)
    if u0 is not None:
        u_st = _hier_gather(h.u_inj, np.asarray(u0, np.float32), 0.0)
        u_st *= h.u_inj_flip[:, None]
    state = (jnp.asarray(w_st), jnp.asarray(u_st))
    pleaves = tuple(problem.prox_params[k]
                    for k in sorted(problem.prox_params))
    recv_src = (problem.recv_src_boundary if comm == "boundary"
                else problem.recv_src_dense)
    operands = (problem.tau, problem.node_owned, problem.ends,
                problem.bound_unit, problem.edge_owned, problem.orient,
                problem.send_idx, problem.send_flip, recv_src,
                problem.recv_flip) + pleaves

    if tol is None or num_iters == 0:
        run = _make_hier_run(problem, mesh, lam, axis=axis, rho=rho,
                             comm=comm, num_iters=num_iters,
                             with_residual=False, reg=reg)
        w_fin, u_fin = run(*state, *operands)
        iterations = num_iters
    else:
        chunk = int(tol_every) if tol_every else min(50, num_iters)
        runs = {}

        def run_chunk(st, r0, r1):
            length = r1 - r0
            if length not in runs:
                runs[length] = _make_hier_run(
                    problem, mesh, lam, axis=axis, rho=rho, comm=comm,
                    num_iters=length, with_residual=True, reg=reg)
            w_, u_, res = runs[length](*st, *operands)
            return (w_, u_), (), np.max(np.asarray(res))

        (w_fin, u_fin), _traces, iterations, _ = run_chunked(
            run_chunk, state, total=num_iters, chunk_size=chunk, tol=tol)

    w = np.asarray(w_fin)[h.w_sel]
    u = np.asarray(u_fin)[h.u_sel] * h.u_flip[:, None]
    return w, u, iterations, comm


def solve_and_unpermute(graph: EmpiricalGraph, data: L.NodeData, mesh: Mesh,
                        lam: float, num_iters: int, **kw) -> np.ndarray:
    """Deprecated shim: shard, solve, return W in the original node order.

    Thin adapter over the unified API — equivalent to
    ``Solver(SolverConfig(backend="sharded", mesh=mesh, ...)).run(problem)``;
    prefer that surface for new code (it also returns duals, traces, and
    diagnostics).
    """
    import warnings

    from repro.api import Problem, Solver, SolverConfig

    warnings.warn(
        "solve_and_unpermute is deprecated; use repro.api.Solver with "
        "SolverConfig(backend='sharded')", DeprecationWarning, stacklevel=2)

    cfg = SolverConfig(
        backend="sharded", mesh=mesh, num_iters=num_iters,
        mesh_axis=kw.pop("axis", "data"), rho=kw.pop("rho", 1.0),
        comm=kw.pop("comm", "dense"),
        partitioner=kw.pop("partitioner", "cluster"))
    if kw:
        raise TypeError(f"unexpected arguments {sorted(kw)}")
    res = Solver(cfg).run(Problem.create(graph, data, lam))
    return np.asarray(res.w)
