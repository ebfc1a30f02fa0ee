"""Public entry points for the Pallas kernels.

Each op dispatches to the Pallas kernel on TPU and to ``interpret=True``
(or the jnp reference for speed, where noted) elsewhere, so the same call
sites work in CPU tests and on real hardware.  Off-TPU,
``REPRO_FORCE_INTERPRET=1`` runs the kernels in interpret mode instead of
the references (used by the kernel test-suite); on a TPU it has no
effect.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.pd_step import fused_pd_step as _fused_pd_step
from repro.kernels.ridge_prox import batched_affine as _affine
from repro.kernels.tv_prox import tv_prox as _tv_prox


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode off-TPU only: on a TPU the kernels always compile
    (``REPRO_FORCE_INTERPRET`` cannot move them off the chip)."""
    return not _on_tpu()


def _use_kernel_default() -> bool:
    """Kernel path on TPU; jnp reference elsewhere (interpret-mode Pallas
    on CPU is orders of magnitude slower than the XLA reference, which is
    what the CI conformance matrix would otherwise pay on every solve).
    ``REPRO_FORCE_INTERPRET=1`` runs the kernels (in interpret mode)
    off-TPU too."""
    return _on_tpu() or bool(os.environ.get("REPRO_FORCE_INTERPRET"))


def tv_prox(u: jnp.ndarray, bound: jnp.ndarray, *,
            interpret: bool | None = None,
            block_e: int | None = None) -> jnp.ndarray:
    """Edge-wise dual clip (Algorithm 1 step 10): kernel on TPU, jnp
    reference elsewhere.  ``block_e`` is a kernel tiling choice —
    semantics-free, so the reference branch accepts and ignores it."""
    kw = {} if block_e is None else {"block_e": block_e}
    if interpret is not None:            # explicit request: run the kernel
        return _tv_prox(u, bound, interpret=interpret, **kw)
    if _use_kernel_default():
        return _tv_prox(u, bound, interpret=_interpret(), **kw)
    return _ref.tv_prox_ref(u, bound.astype(u.dtype)).astype(u.dtype)


def batched_affine(p: jnp.ndarray, v: jnp.ndarray, *,
                   interpret: bool | None = None,
                   block_v: int | None = None) -> jnp.ndarray:
    """Node-wise ridge primal update w_i = P_i v_i (paper eq. 21):
    kernel on TPU, jnp reference elsewhere.  ``block_v`` is a kernel
    tiling choice — semantics-free, ignored on the reference branch."""
    kw = {} if block_v is None else {"block_v": block_v}
    if interpret is not None:            # explicit request: run the kernel
        return _affine(p, v, interpret=interpret, **kw)
    if _use_kernel_default():
        return _affine(p, v, interpret=_interpret(), **kw)
    return _ref.batched_affine_ref(p, v).astype(v.dtype)


def pd_step(w_store, u_store, ends, params, tau, sigma, la, *, loss, reg,
            pkeys, block_nodes, block_edges, kn, klo, khi, rho=1.0,
            iters=1, compute_residual=False,
            use_kernel: bool | None = None):
    """Fused primal-dual step over an edge-blocked layout (Algorithm 1
    body in one pass): Pallas kernel on TPU, the bit-comparable jnp
    reference elsewhere.  ``params`` is the tuple of ``loss.prox_setup``
    leaves in ``pkeys`` (sorted-key) order; shapes per
    ``kernels.ref.fused_pd_step_ref``.  With ``compute_residual`` the
    return gains the call's f32 eq.-11 residual scalar (computed
    in-kernel on the kernel path)."""
    if use_kernel is None:
        use_kernel = _use_kernel_default()
    fn = _fused_pd_step if use_kernel else _ref.fused_pd_step_ref
    kw = dict(loss=loss, reg=reg, pkeys=pkeys, block_nodes=block_nodes,
              block_edges=block_edges, kn=kn, klo=klo, khi=khi, rho=rho,
              iters=iters, compute_residual=compute_residual)
    if use_kernel:
        kw["interpret"] = _interpret()
    return fn(w_store, u_store, ends, params, tau, sigma, la, **kw)
