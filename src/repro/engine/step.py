"""The canonical primal-dual iteration (paper Algorithm 1, eqs. 14-15).

This module is the *single* statement of the iteration math in the whole
repository.  One step is four typed primitives over a
:class:`GraphExecutor`:

    gather duals   dtu = D^T u            (executor.gather_duals)
    primal prox    w+  = PU(w - T dtu)    (loss prox, eq. 17)
    edge diff      dw  = D (2 w+ - w)     (executor.edge_diff)
    dual prox      u+  = prox_{sigma dg*}(u + Sigma dw)   (step 10)

plus the Krasnosel'skii-Mann relaxation folded in when ``rho != 1``.
Every backend realizes the same step by supplying an executor for *how*
the two graph operators run on its substrate:

  * dense gather-sum        (``executors.DenseExecutor``),
  * edge-blocked VMEM window (``executors.WindowExecutor`` — the fused
    Pallas kernel's in-kernel body runs :func:`pd_step` on its loaded
    window via this executor),
  * shard_map halo exchange  (``executors.HaloExecutor``),
  * federated mailboxes      (``executors.MailboxExecutor``).

The executor also duck-types as the ``graph`` argument of the
regularizer resolvents: it exposes ``weights`` (the per-owned-edge A_e
in the executor's own edge order), which is all ``dual_prox`` /
``project_dual`` read.
"""
from __future__ import annotations

from typing import Callable, Protocol, runtime_checkable

import jax.numpy as jnp

from repro.obs import profile as _prof
from repro.obs.profile import annotate as _scope


def ensure_column(x):
    """(N,) -> (N, 1); scalars and already-columned arrays pass through.

    The engine's one shape convention: per-node/per-edge coefficient
    vectors broadcast against (N, n) signals as columns.  Shared with
    the regularizer resolvents, which see 1-D weights from a real graph
    and pre-columned 2-D windows from the fused kernel.
    """
    if jnp.ndim(x) == 1:
        return x[:, None]
    return x


_col = ensure_column


@runtime_checkable
class GraphExecutor(Protocol):
    """How one backend realizes the two graph operators of Algorithm 1.

    ``weights`` carries the per-owned-edge A_e (executor edge order), so
    the executor can stand in for the graph inside the regularizer's
    dual resolvent.  ``owned_duals`` maps the dual state the gather
    reads to the dual rows this executor updates — identity everywhere
    except the VMEM window executor, whose gather state includes halo
    rows.
    """

    weights: jnp.ndarray

    def gather_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        """D^T u: (E', n) dual state -> (V', n) node aggregate."""
        ...

    def edge_diff(self, z: jnp.ndarray) -> jnp.ndarray:
        """D z: (V', n) node signal -> (E_owned, n) edge differences."""
        ...

    def owned_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        """The (E_owned, n) rows of ``u`` this executor updates."""
        ...


def pd_step(executor: GraphExecutor, prox: Callable, regularizer, lam,
            tau: jnp.ndarray, sigma: jnp.ndarray, w: jnp.ndarray,
            u: jnp.ndarray, *, rho: float = 1.0,
            clip_fn: Callable | None = None,
            primal_update: Callable | None = None):
    """One primal-dual step — the single source of truth for eqs. 14-15.

    primal (eq. 17):  w+ = PU(w - T D^T u)
    dual  (step 10):  u+ = prox_{sigma dg*}(u + Sigma D (2 w+ - w))
    KM relaxation:    x  <- x + rho (x+ - x)  (duals re-projected)

    ``primal_update(prox, w, dtu, tau)`` overrides the one-prox primal
    (the federated runtime plugs its local-update policy here);
    ``clip_fn`` routes the dual resolvent through a custom kernel.
    Returns ``(w_new, u_new)`` with ``u_new`` over the executor's owned
    edges.
    """
    tau_c = _col(tau)
    sigma_c = _col(sigma)
    # named scopes map device profiles onto the paper phases
    # (repro.obs.profile); they cost nothing at runtime
    with _scope(_prof.PHASE_GATHER):
        dtu = executor.gather_duals(u)
    with _scope(_prof.PHASE_PRIMAL):
        if primal_update is None:
            w_new = prox(w - tau_c * dtu)
        else:
            w_new = primal_update(prox, w, dtu, tau)
    with _scope(_prof.PHASE_EDGE_DIFF):
        dw = executor.edge_diff(2.0 * w_new - w)
    with _scope(_prof.PHASE_DUAL):
        u_own = executor.owned_duals(u)
        u_new = regularizer.dual_prox(u_own + sigma_c * dw, executor, lam,
                                      sigma, clip_fn=clip_fn)
    if rho != 1.0:
        with _scope(_prof.PHASE_RELAX):
            w_new = w + rho * (w_new - w)
            u_new = regularizer.project_dual(
                u_own + rho * (u_new - u_own), executor, lam)
    return w_new, u_new


def pd_residual(tau, sigma, w, u, w_new, u_new) -> jnp.ndarray:
    """Scaled fixed-point residual of the PD operator — the eq.-11 proxy.

    At a solution the iteration is stationary, and the coupled optimality
    conditions (paper eq. 11) hold exactly; the preconditioned step
    lengths make ``|w+ - w| / tau`` a bound on the primal stationarity
    gap and ``|u+ - u| / sigma`` on the dual one.  The max norm is
    order-independent, so every backend computes the identical residual
    from identical iterates regardless of its node/edge layout.
    """
    with _scope(_prof.PHASE_RESIDUAL):
        rp = jnp.max(jnp.abs(w_new - w) / _col(tau))
        rd = jnp.max(jnp.abs(u_new - u) / _col(sigma))
        return jnp.maximum(rp, rd)


def certificate(problem, w: jnp.ndarray, u: jnp.ndarray) -> dict:
    """Optimality diagnostics from the coupled conditions (paper eq. 11).

    * dual feasibility (regularizer-defined; <= 0 means feasible),
    * stationarity residual at labeled nodes for the squared loss,
    * for squared loss + TV, the *true* duality gap ``optimality_gap``
      (see :func:`optimality_gap`) — an upper bound on P(w) - P*.
    """
    from repro.api.losses import SquaredLoss
    from repro.api.regularizers import TotalVariation

    diag = {"dual_infeasibility": problem.regularizer.dual_infeasibility(
        u, problem.graph, problem.lam)}
    if isinstance(problem.loss, SquaredLoss):
        data = problem.data
        pred = jnp.einsum("vmn,vn->vm", data.x, w, precision="highest")
        r = (pred - data.y) * data.sample_mask
        grad = 2.0 * jnp.einsum("vm,vmn->vn", r, data.x,
                                precision="highest") / data.counts()[:, None]
        grad = grad * data.labeled_mask[:, None]
        station = grad + (problem.graph.incidence_transpose_apply(u)
                          * data.labeled_mask[:, None])
        diag["stationarity_residual_labeled"] = jnp.max(jnp.abs(station))
        if isinstance(problem.regularizer, TotalVariation):
            diag["optimality_gap"] = optimality_gap(problem, w, u)
    return diag


def optimality_gap(problem, w: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """True eq.-11 duality gap for squared loss + TV: ``P(w) - g(u)``.

    The Lagrangian dual of GTVMin at a feasible dual point (|u_e| <=
    lam A_e componentwise, the conjugate domain of the lam-scaled
    anisotropic TV) is

        g(u) = sum_i  min_{w_i in B_R} [ ell_i(w_i) + z_i^T w_i ],
        z = D^T u,

    with ``ell_i`` the per-node squared loss (zero at unlabeled nodes).
    The ball ``B_R`` with ``R = 2 max_i |w_i|_2 + 1`` encodes the one
    assumption — the minimizer lies inside it (any GTVMin solution is
    bounded by the data, and at convergence the iterate is the
    minimizer, so the margin holds) — which keeps every per-node min
    finite even for singular node covariances.  Labeled nodes solve the
    regularized normal equations via the pseudo-inverse
    (:func:`psd_pinv_solve`) and correct for curvature
    null-space components with the first-order ball bound
    ``min >= f(w*) - 2R |grad f(w*)|``; unlabeled nodes are exact:
    ``-R |z_i|``.  Weak duality gives ``P(w) - P* <= gap`` for every
    iterate, so the gap is a *certified* bound, unlike the fixed-point
    residual proxy.  Returns an f32 scalar (can be slightly negative at
    machine precision when w is optimal).
    """
    data = problem.data
    lam_a = problem.lam * problem.graph.weights
    u_feas = jnp.clip(u, -lam_a[:, None], lam_a[:, None])
    z = problem.graph.incidence_transpose_apply(u_feas)        # (V, n)
    cnt = data.counts()[:, None]
    xm = data.x * data.sample_mask[..., None]
    q = jnp.einsum("vmn,vmk->vnk", xm, data.x,
                   precision="highest") / cnt[..., None]
    c = jnp.einsum("vmn,vm->vn", xm, data.y, precision="highest") / cnt
    yty = jnp.sum(data.y ** 2 * data.sample_mask, axis=1) / cnt[:, 0]
    radius = 2.0 * jnp.max(jnp.linalg.norm(w, axis=1)) + 1.0

    rhs = c - 0.5 * z
    w_star = psd_pinv_solve(q, rhs)
    lval = (jnp.einsum("vn,vnk,vk->v", w_star, q, w_star, precision="highest")
            - 2.0 * jnp.sum(c * w_star, axis=1) + yty)
    # grad of f(w) = ell(w) + z^T w at w*: 2 (Q w* - rhs)
    grad = 2.0 * (jnp.einsum("vnk,vk->vn", q, w_star, precision="highest")
                  - rhs)
    g_lab = (lval + jnp.sum(z * w_star, axis=1)
             - 2.0 * radius * jnp.linalg.norm(grad, axis=1))
    g_unl = -radius * jnp.linalg.norm(z, axis=1)
    g = jnp.sum(jnp.where(data.labeled_mask > 0, g_lab, g_unl))
    return (problem.objective(w) - g).astype(jnp.float32)


def psd_pinv_solve(q: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """``pinv(q_i) @ rhs_i`` for a batch of symmetric PSD matrices.

    Through the eigendecomposition, with ``jnp.linalg.pinv``'s cutoff
    (eigenvalues at or below ``10 n eps`` times the largest are dropped).
    ``jnp.linalg.pinv`` itself takes an SVD, which the TPU compiler
    cannot fit in its scoped VMEM for 250k or more batched 2x2 matrices.
    """
    s, v = jnp.linalg.eigh(q)                      # q = v diag(s) v^T
    cutoff = (10 * q.shape[-1] * jnp.finfo(q.dtype).eps
              * jnp.max(jnp.abs(s), axis=-1, keepdims=True))
    keep = jnp.abs(s) > cutoff
    inv_s = jnp.where(keep, 1.0 / jnp.where(keep, s, 1.0), 0.0)
    coef = jnp.sum(v * rhs[..., :, None], axis=-2) * inv_s     # v^T rhs
    return jnp.sum(v * coef[..., None, :], axis=-1)
