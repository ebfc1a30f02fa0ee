"""Declarative problem / config / result containers for the unified solver.

A :class:`Problem` is everything eq. (4) needs — the empirical graph, the
batched node-local datasets, the TV strength lambda, plus the two template
slots (a :class:`~repro.api.losses.Loss` and a
:class:`~repro.api.regularizers.Regularizer`).  It is a pytree whose array
leaves (graph, data, lambda) are traced and whose template slots are static
aux data, so Problems flow through ``jax.jit`` / ``jax.vmap`` unchanged —
``solve_path`` vmaps one Problem over a whole lambda path.

:class:`SolverConfig` carries the *how* (iterations, over-relaxation,
continuation schedule, metric cadence, backend selection) and
:class:`SolveResult` is the single result pytree every backend returns.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.api.losses import Loss, SquaredLoss, get_loss
from repro.api.regularizers import Regularizer, TotalVariation, \
    get_regularizer
from repro.core.graph import EmpiricalGraph
from repro.core.losses import NodeData


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Problem:
    """One networked-learning instance: min_w E_hat(w) + lam * g(D w)."""

    graph: EmpiricalGraph
    data: NodeData
    lam: jnp.ndarray | float = 1e-3
    loss: Loss = SquaredLoss()
    regularizer: Regularizer = TotalVariation()

    # -- pytree plumbing (loss/regularizer are static template slots) -------
    def tree_flatten(self):
        return (self.graph, self.data, self.lam), (self.loss,
                                                   self.regularizer)

    @classmethod
    def tree_unflatten(cls, aux, children):
        graph, data, lam = children
        loss, regularizer = aux
        return cls(graph=graph, data=data, lam=lam, loss=loss,
                   regularizer=regularizer)

    # -- construction --------------------------------------------------------
    @classmethod
    def create(cls, graph: EmpiricalGraph, data: NodeData, lam=1e-3, *,
               loss="squared", regularizer="tv", **loss_kwargs) -> "Problem":
        """Build a Problem resolving registry names for the template slots.

        ``loss`` / ``regularizer`` accept instances or registry names;
        extra kwargs (``alpha``, ``num_inner``) configure a named loss.
        """
        return cls(graph=graph, data=data, lam=lam,
                   loss=get_loss(loss, **loss_kwargs),
                   regularizer=get_regularizer(regularizer))

    def with_lam(self, lam) -> "Problem":
        """Same instance at a different TV strength (lambda-path helper)."""
        return dataclasses.replace(self, lam=lam)

    # -- objective -----------------------------------------------------------
    def objective(self, w: jnp.ndarray) -> jnp.ndarray:
        """Primal objective E_hat(w) + lam * g(D w) (paper eq. 4)."""
        return (self.loss.empirical_error(self.data, w)
                + self.regularizer.value(self.graph, w, self.lam))

    @property
    def num_nodes(self) -> int:
        return self.data.num_nodes

    @property
    def num_features(self) -> int:
        return self.data.num_features


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """How to run Algorithm 1 (everything static / Python-side).

    Core iteration:
      num_iters:    primal-dual iterations (ignored when continuation=True).
      rho:          Krasnosel'skii-Mann over-relaxation in (0, 2); ~1.9
                    roughly doubles per-iteration progress (EXPERIMENTS.md).
      metric_every: objective/MSE cadence; must divide the iteration count.
                    Traces then have length num_iters // metric_every.
      tol:          residual-based early stopping (None disables).  The
                    solve advances in metric_every-sized compiled chunks
                    and stops at the first chunk whose max per-iteration
                    eq.-11 fixed-point residual (engine.pd_residual: the
                    tau/sigma-scaled max-norm change of one iteration)
                    is <= tol; num_iters becomes the budget ceiling.
                    Implemented once in repro.engine and honoured by
                    every backend; the stopping iteration lands in
                    ``diagnostics["iterations"]``.  Traces then have
                    length iterations // metric_every.
      record_residual: record the eq.-11 fixed-point residual
                    (engine.pd_residual) in ``SolveResult.residual`` at
                    the metric cadence even without ``tol`` — the
                    certificate-decay trace reports and the serving
                    layer read.  tol runs always carry the residual
                    trace (the stopping test computes it anyway);
                    dense/pallas backends only.

    Continuation (beyond-paper warm-start schedule, see
    ``core.nlasso.nlasso_continuation`` for the rationale):
      continuation: solve first at warm_lam (default 10x target clipped to
                    [1e-2, 1]), re-project the duals, then solve at the
                    target lambda.
      warm_lam / warm_iters / final_iters: the schedule.

    Backend dispatch:
      backend:     "dense" (single-program lax.scan), "sharded" (shard_map
                   message passing), or "pallas" (dense with the TPU
                   kernels auto-wired).
      fused:       pallas backend only — run the fused primal-dual Pallas
                   kernel over the edge-blocked graph layout instead of
                   the four unfused HBM round-trips per iteration.  None
                   (default) resolves to True on TPU, False elsewhere;
                   ``REPRO_FUSED=1`` / ``REPRO_FUSED=0`` (env) overrides
                   the default either way.  Falls back to the unfused
                   path for losses/regularizers without a fused form
                   (anything but squared + TV) or when custom kernel
                   hooks are set.
      mesh / mesh_axis / num_shards / partitioner / comm: sharded-backend
                   layout knobs (mesh defaults to every device of the
                   process, all on the "data" axis).
                   ``comm`` is "auto" (boundary exchange when the
                   inter-shard cut fraction is < 25%, dense otherwise),
                   "dense", or "boundary".
      federated:   federated-backend runtime knobs: a
                   ``repro.federated.FederatedConfig`` whose participation
                   / local-update / compression / checkpoint policies are
                   used as-is while this config's num_iters, rho,
                   metric_every, and compute_diagnostics override the
                   loop shape.  None runs the synchronous
                   full-participation defaults (the dense oracle mode).
      clip_fn / affine_fn: custom kernel hooks for the dual clip and the
                   affine primal update (dense/pallas backends; the pallas
                   backend fills unset hooks with the stock TPU kernels).
                   Prefer ``backend="pallas"`` unless you need a
                   non-standard kernel.

    Precision policy:
      dtype:       storage dtype for the iteration state on the fused
                   pallas path: "float32" (default) or "bfloat16".
                   bf16 stores ``w`` / ``u`` and the prox parameters at
                   2 bytes — halving the HBM<->VMEM window traffic —
                   while every incidence contraction, prox solve, and
                   dual resolvent still
                   *accumulates* in f32 (upcast at the VMEM window
                   boundary, see ``kernels.ref.pd_window_step``).
                   Returned ``w`` / ``u`` and all traces are f32.  Note
                   bf16 quantizes each iterate, so residuals floor near
                   bf16 resolution (~3e-3 relative): pair bf16 with a
                   ``tol`` no tighter than that.  Backends other than
                   the fused pallas path reject non-f32 dtypes.
    """

    num_iters: int = 500
    rho: float = 1.0
    metric_every: int = 1
    tol: float | None = None
    record_residual: bool = False
    # continuation schedule
    continuation: bool = False
    warm_lam: float | None = None
    warm_iters: int = 3000
    final_iters: int = 1000
    # backend dispatch
    backend: str = "dense"
    fused: bool | None = None
    mesh: Any = dataclasses.field(default=None, compare=False, repr=False)
    mesh_axis: str = "data"
    num_shards: int | None = None
    partitioner: str = "cluster"
    comm: str = "auto"
    federated: Any = None
    # custom kernel hooks
    clip_fn: Any = dataclasses.field(default=None, compare=False,
                                     repr=False)
    affine_fn: Any = dataclasses.field(default=None, compare=False,
                                       repr=False)
    # eq.-11 certificate on the result (disabled internally for
    # warm-phase solves whose result is discarded)
    compute_diagnostics: bool = True
    # storage dtype for the fused-path iteration state ("float32" or
    # "bfloat16"); accumulation is always f32
    dtype: str = "float32"

    def replace(self, **kw) -> "SolverConfig":
        return dataclasses.replace(self, **kw)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SolveResult:
    """What every backend returns.

    Attributes:
      w:           (V, n) final primal weights (original node order).
      u:           (E, n) final dual edge variables (original edge order).
      objective:   (T,) primal-objective trace (T = iters / metric_every;
                   length 1 for the sharded backend, which evaluates
                   metrics once at the final iterate).
      mse:         (T,) eq.-24 MSE trace vs. w_true, or None.
      lam:         the TV strength solved at (scalar; (L,) after
                   ``solve_path``).
      diagnostics: optimality certificate (eq. 11): ``dual_infeasibility``
                   always; ``stationarity_residual_labeled`` for the
                   squared loss.
      residual:    (T,) eq.-11 fixed-point residual trace at the metric
                   cadence (the certificate-decay curve; its last entry
                   is the per-response serving SLA).  Populated on tol
                   runs and ``record_residual`` runs of the dense/pallas
                   backends, else None.
    """

    w: jnp.ndarray
    u: jnp.ndarray
    objective: jnp.ndarray
    mse: jnp.ndarray | None
    lam: jnp.ndarray | float
    diagnostics: dict
    residual: jnp.ndarray | None = None

    def tree_flatten(self):
        return (self.w, self.u, self.objective, self.mse, self.lam,
                self.diagnostics, self.residual), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def final_objective(self) -> jnp.ndarray:
        return self.objective[-1]
