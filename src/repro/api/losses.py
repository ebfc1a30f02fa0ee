"""Pluggable local losses — the single home of the loss numerics.

Paper §4: Algorithm 1 is a *template*; a concrete federated learning
algorithm is obtained by choosing the local loss L(X^(i), w) and hence the
node-wise primal update operator (eq. 18)

    PU_i(v) = argmin_z  L(X^(i), z) + (1/(2 tau_i)) ||v - z||^2 .

A :class:`Loss` bundles everything the engine needs from that choice:

  * ``node_values(data, w)`` — the per-node loss values (eq. 2 summands),
  * ``prox_setup(data, tau)`` — precompute the per-node prox parameters
    as a flat dict of ``(V, ...)`` arrays (every leaf at least 2-D, so
    the fused kernel can window-slice them uniformly),
  * ``prox_apply(params, v)`` — evaluate PU batched over nodes from the
    precomputed parameters (this is what runs *inside* the fused Pallas
    kernel's VMEM window),
  * ``make_prox(data, tau)`` — the closed-over convenience composition
    of the two.

Implemented losses (paper §4.1-4.3):
  * squared error (eq. 20)   -> closed-form batched ridge solve (eq. 21)
  * Lasso (eq. 22)           -> ISTA inner loop (high-dim m_i << n regime)
  * logistic (eq. 23)        -> damped-Newton inner loop (no closed form)

Losses are small frozen dataclasses, so they are hashable and ride through
``jax.jit`` as static arguments.  ``kernel_safe`` marks losses whose
``prox_apply`` lowers inside a Pallas TPU kernel — all three stock
losses qualify (the logistic Newton system is solved by an explicit
unrolled small-n Cholesky instead of ``jnp.linalg.solve``, which has no
Pallas lowering).  Registering a new loss
makes it reachable from every backend via ``Problem.create(...,
loss="<name>")`` — the model-agnostic plug-in point of *Towards
Model-Agnostic Federated Learning over Networks*.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, ClassVar

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve

from repro.core.graph import vmem_words
from repro.core.losses import NodeData

# (m, n)-shaped temporaries of the logistic prox's Newton step that the
# v5e compiler keeps live in the fused kernel (fitted with the window
# model, core.graph.fused_window_bytes)
_LOGISTIC_TEMPS = 4

LOSSES: dict[str, type] = {}


def register_loss(name: str):
    """Class decorator adding a Loss subclass to the registry."""
    def deco(cls):
        cls.name = name
        LOSSES[name] = cls
        return cls
    return deco


def get_loss(spec, **kwargs) -> "Loss":
    """Resolve a Loss instance from an instance or a registry name.

    Extra keyword arguments configure the loss when ``spec`` is a name
    (e.g. ``get_loss("lasso", alpha=0.02)``); they must be empty when an
    instance is passed.
    """
    if isinstance(spec, Loss):
        if kwargs:
            raise TypeError("loss kwargs only apply to registry names")
        return spec
    if isinstance(spec, str):
        try:
            cls = LOSSES[spec]
        except KeyError:
            raise ValueError(
                f"unknown loss {spec!r}; registered: {sorted(LOSSES)}")
        return cls(**kwargs)
    raise TypeError(f"loss must be a Loss or a registry name, got {spec!r}")


def _soft_threshold(z: jnp.ndarray, t) -> jnp.ndarray:
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - t, 0.0)


def _chol_solve(a: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """Batched SPD solve via an explicit unrolled Cholesky factorization.

    ``a`` (V, n, n) symmetric positive definite, ``rhs`` (V, n) ->
    (V, n) solving ``a @ z = rhs`` per node.  The feature count n is
    small and static, so the Cholesky-Banachiewicz recurrence and the
    two triangular substitutions unroll at trace time into pure
    elementwise arithmetic over the node axis — no ``jnp.linalg``
    primitives, which is what lets callers (the logistic Newton step)
    lower inside a Pallas TPU kernel where LU / triangular-solve ops
    have no mosaic lowering.
    """
    n = a.shape[-1]
    lo = [[None] * n for _ in range(n)]
    for i in range(n):
        for j in range(i + 1):
            s = a[..., i, j]
            for k in range(j):
                s = s - lo[i][k] * lo[j][k]
            lo[i][j] = jnp.sqrt(s) if i == j else s / lo[j][j]
    # forward substitution  L c = rhs
    c = [None] * n
    for i in range(n):
        s = rhs[..., i]
        for k in range(i):
            s = s - lo[i][k] * c[k]
        c[i] = s / lo[i][i]
    # back substitution  L^T z = c
    z = [None] * n
    for i in reversed(range(n)):
        s = c[i]
        for k in range(i + 1, n):
            s = s - lo[k][i] * z[k]
        z[i] = s / lo[i][i]
    return jnp.stack(z, axis=-1)


@dataclasses.dataclass(frozen=True)
class Loss:
    """Local loss interface (paper §4 template slot)."""

    name: ClassVar[str] = "base"
    # prox_apply lowers inside a Pallas TPU kernel (no unsupported
    # primitives such as jnp.linalg.solve)
    kernel_safe: ClassVar[bool] = False

    def node_values(self, data: NodeData, w: jnp.ndarray) -> jnp.ndarray:
        """Per-node loss L(X^(i), w^(i)): (V,)."""
        raise NotImplementedError

    def empirical_error(self, data: NodeData, w: jnp.ndarray) -> jnp.ndarray:
        """E_hat(w) = sum_{i in M} L(X^(i), w^(i))  (paper eq. 2)."""
        return jnp.sum(self.node_values(data, w) * data.labeled_mask)

    def prox_setup(self, data: NodeData, tau: jnp.ndarray) -> dict:
        """Precompute the batched primal-update parameters.

        Returns a flat ``{name: (V, ...)}`` dict whose leaves all have
        ``ndim >= 2`` and a leading node axis, so every executor (dense,
        sharded rows, fused VMEM windows) can slice them uniformly.
        """
        raise NotImplementedError

    def prox_apply(self, params: dict, v: jnp.ndarray, *,
                   affine_fn: Callable | None = None) -> jnp.ndarray:
        """Evaluate PU (eq. 18) batched over nodes: (V, n) -> (V, n).

        ``affine_fn`` routes affine-map losses through the Pallas
        ``batched_affine`` kernel; iterative losses ignore it.
        """
        raise NotImplementedError

    def prox_param_floats(self, num_samples: int, num_features: int) -> int:
        """Per-node f32 words the ``prox_setup`` leaves and the prox's
        in-kernel temporaries take in a fused VMEM window, tiled
        (``core.graph.vmem_words``; VMEM budgeting)."""
        raise NotImplementedError

    def make_prox(self, data: NodeData, tau: jnp.ndarray, *,
                  affine_fn: Callable | None = None) -> Callable:
        """Batched primal-update operator PU (eq. 18): (V, n) -> (V, n)."""
        params = self.prox_setup(data, tau)

        def prox(v: jnp.ndarray) -> jnp.ndarray:
            return self.prox_apply(params, v, affine_fn=affine_fn)

        return prox


@register_loss("squared")
@dataclasses.dataclass(frozen=True)
class SquaredLoss(Loss):
    """Squared error (paper §4.1, eq. 20) — closed-form ridge prox (eq. 21)."""

    kernel_safe: ClassVar[bool] = True

    def node_values(self, data, w):
        pred = jnp.einsum("vmn,vn->vm", data.x, w, precision="highest")
        res = (data.y - pred) ** 2 * data.sample_mask
        return jnp.sum(res, axis=1) / data.counts()

    def prox_setup(self, data, tau):
        """Precompute eq. 21 as an affine map.

        PU_i(v) = (I + (2 tau_i / m_i) Q_i)^{-1} (v + (2 tau_i / m_i)
        X_i^T y_i) with Q_i = X_i^T X_i; returns ``{"p": (V, n, n),
        "b": (V, n)}`` such that PU_i(v) = P_i @ (v + b_i).  Unlabeled
        nodes get P = I, b = 0.
        """
        xm = data.x * data.sample_mask[..., None]
        q = jnp.einsum("vmn,vmk->vnk", xm, data.x,
                       precision="highest")                   # (V, n, n)
        xty = jnp.einsum("vmn,vm->vn", xm, data.y,
                         precision="highest")                 # (V, n)
        c = (2.0 * tau / data.counts())[:, None]              # (V, 1)
        n = data.num_features
        eye = jnp.eye(n, dtype=data.x.dtype)
        a = eye[None] + c[..., None] * q                      # SPD
        # through a Cholesky factor, not jnp.linalg.inv: the TPU compiler
        # takes minutes over the batched LU at 1M nodes
        p = cho_solve((jnp.linalg.cholesky(a), True),
                      jnp.broadcast_to(eye, a.shape))
        b = c * xty
        lab = data.labeled_mask
        p = jnp.where(lab[:, None, None] > 0, p, eye[None])
        b = jnp.where(lab[:, None] > 0, b, 0.0)
        return {"p": p, "b": b}

    def prox_apply(self, params, v, *, affine_fn=None):
        vb = v + params["b"]
        if affine_fn is not None:
            return affine_fn(params["p"], vb)
        return jnp.einsum("vnk,vk->vn", params["p"], vb, precision="highest")

    def prox_param_floats(self, num_samples, num_features):
        n = num_features
        return vmem_words((n, n), (n,))


@register_loss("lasso")
@dataclasses.dataclass(frozen=True)
class LassoLoss(Loss):
    """Lasso (paper §4.2, eq. 22) — ISTA inner loop for the m_i << n regime.

    ``alpha`` is the local l1 weight (lambda inside eq. 22; renamed to
    avoid clashing with the TV strength).  The smooth part has per-node
    Lipschitz constant L_i = 2 lambda_max(Q_i)/m_i + 1/tau_i; ISTA takes
    steps 1/L_i and soft-thresholds with alpha/L_i.
    """

    alpha: float = 0.0
    num_inner: int = 50

    kernel_safe: ClassVar[bool] = True

    def node_values(self, data, w):
        return (SquaredLoss().node_values(data, w)
                + self.alpha * jnp.sum(jnp.abs(w), axis=1))

    def prox_setup(self, data, tau):
        xm = data.x * data.sample_mask[..., None]
        q = jnp.einsum("vmn,vmk->vnk", xm, data.x, precision="highest")
        xty = jnp.einsum("vmn,vm->vn", xm, data.y, precision="highest")
        m = data.counts()
        # lambda_max via eigvalsh (setup-time only; n is small)
        lam_max = jnp.linalg.eigvalsh(q)[:, -1]
        lips = 2.0 * lam_max / m + 1.0 / tau                  # (V,)
        return {"q": q, "xty": xty, "m": m[:, None],
                "step": (1.0 / lips)[:, None], "tau": tau[:, None],
                "labeled": data.labeled_mask[:, None]}

    def prox_apply(self, params, v, *, affine_fn=None):
        del affine_fn                       # iterative inner solver
        q, xty = params["q"], params["xty"]
        m, step, tau = params["m"], params["step"], params["tau"]

        def body(_, z):
            grad = 2.0 * (jnp.einsum("vnk,vk->vn", q, z, precision="highest")
                          - xty) / m
            grad = grad + (z - v) / tau
            return _soft_threshold(z - step * grad, self.alpha * step)

        z = jax.lax.fori_loop(0, self.num_inner, body, v)
        return jnp.where(params["labeled"] > 0, z, v)

    def prox_param_floats(self, num_samples, num_features):
        n = num_features
        return vmem_words((n, n), (n,), (1,), (1,), (1,), (1,))


@register_loss("logistic")
@dataclasses.dataclass(frozen=True)
class LogisticLoss(Loss):
    """Logistic (paper §4.3, eq. 23) — damped-Newton inner loop.

    The objective  L_i(z) + (1/(2 tau_i))||z - v||^2  is smooth and
    strongly convex; n is small, so a handful of exact Newton steps
    converge to machine precision (the paper's remark that the updates
    are robust to inexact resolvent evaluation).  The Newton system is
    solved by the explicit small-n Cholesky (:func:`_chol_solve` —
    exact, and the regularized Hessian ``H + I/tau`` is SPD by
    construction) rather than ``jnp.linalg.solve``, so ``kernel_safe``
    is True and logistic rides the fused Pallas kernel on real TPU.
    """

    num_inner: int = 8

    kernel_safe: ClassVar[bool] = True

    def node_values(self, data, w):
        logits = jnp.einsum("vmn,vn->vm", data.x, w, precision="highest")
        # numerically-stable BCE with logits
        per = jnp.maximum(logits, 0.0) - logits * data.y + jnp.log1p(
            jnp.exp(-jnp.abs(logits)))
        return jnp.sum(per * data.sample_mask, axis=1) / data.counts()

    def prox_setup(self, data, tau):
        return {"x": data.x, "y": data.y, "mask": data.sample_mask,
                "m": data.counts()[:, None], "tau": tau[:, None],
                "labeled": data.labeled_mask[:, None]}

    def prox_apply(self, params, v, *, affine_fn=None):
        del affine_fn                       # iterative inner solver
        x, y, mask = params["x"], params["y"], params["mask"]
        m, tau = params["m"], params["tau"]

        def body(_, z):
            logits = jnp.einsum("vmn,vn->vm", x, z, precision="highest")
            s = jax.nn.sigmoid(logits)
            r = (s - y) * mask                                   # (V, m)
            # a multiply-reduce, not a batched dot: the TPU kernel
            # compiler lowers no dot form of this contraction over m
            grad = jnp.sum(r[..., None] * x, axis=1) / m
            grad = grad + (z - v) / tau
            d = (s * (1 - s)) * mask                             # (V, m)
            hess = jnp.einsum("vmn,vmk->vnk", x * d[..., None], x,
                              precision="highest") / m[..., None]
            n = z.shape[1]
            hess = hess + jnp.eye(n, dtype=z.dtype)[None] / tau[..., None]
            return z - _chol_solve(hess, grad)

        z = jax.lax.fori_loop(0, self.num_inner, body, v)
        return jnp.where(params["labeled"] > 0, z, v)

    def prox_param_floats(self, num_samples, num_features):
        m, n = num_samples, num_features
        # the Newton step's (m, n)-shaped products live beside the
        # leaves: the v5e compiler keeps _LOGISTIC_TEMPS of them
        return (vmem_words((m, n), (m,), (m,), (1,), (1,), (1,))
                + _LOGISTIC_TEMPS * vmem_words((m, n)))


@dataclasses.dataclass(frozen=True)
class CallableLoss(Loss):
    """Adapter for caller-supplied prox operators (legacy entry points).

    Wraps an externally-built ``prox(v)`` while delegating metric values to
    ``base``.  Not registered — exists so ``core.nlasso.solve_nlasso`` can
    keep accepting arbitrary prox callables through the new solver.  No
    ``prox_setup``: the fused backend cannot window an opaque callable,
    so it falls back to the unfused path.
    """

    prox_fn: Callable = None
    base: Loss = None

    def node_values(self, data, w):
        return self.base.node_values(data, w)

    def make_prox(self, data, tau, *, affine_fn=None):
        return self.prox_fn
