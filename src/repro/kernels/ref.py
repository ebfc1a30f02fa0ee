"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the corresponding kernel must
match (tests sweep shapes/dtypes and assert_allclose against these).

The fused primal-dual window step is *not* restated here: it is the
canonical :func:`repro.engine.step.pd_step` evaluated through a
:class:`repro.engine.executors.WindowExecutor`, so the Pallas kernel,
the jnp oracle, and every other backend share one statement of the
iteration math (the bit-parity tests in ``tests/test_engine.py`` and
``tests/test_kernels.py`` pin the kernel to it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.executors import WindowExecutor
from repro.engine.step import pd_step as _engine_pd_step


def tv_prox_ref(u: jnp.ndarray, bound: jnp.ndarray) -> jnp.ndarray:
    """Edge-wise dual clipping T^(lambda A_e) (paper Algorithm 1, step 10).

    u: (E, n) dual edge signal; bound: (E,) per-edge clip level lambda*A_e.
    """
    b = bound[:, None]
    return jnp.clip(u, -b, b)


def batched_affine_ref(p: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Node-wise primal ridge update w_i = P_i @ v_i (paper eq. 21).

    p: (V, n, n); v: (V, n) (already includes the +b_i shift).
    """
    return jnp.einsum("vnk,vk->vn", p, v, precision="highest")


def window_executor(ends_win: jnp.ndarray, num_nodes: int,
                    node_offset, la: jnp.ndarray, *, klo: int,
                    block_edges: int, mxu: bool = False) -> WindowExecutor:
    """The window executor of one grid step: storage endpoints of the
    (EW,) window edges made window-relative by ``node_offset`` (the
    window's first node row).  Built once per window — its incidence
    matrix (``mxu``, a compiled TPU kernel) is loop-invariant across
    fused iterations."""
    return WindowExecutor.from_endpoints(
        ends_win - node_offset, num_nodes, la, klo=klo,
        block_edges=block_edges, mxu=mxu)


def pd_window_step(executor: WindowExecutor, w_win: jnp.ndarray,
                   u_win: jnp.ndarray, params_win: tuple,
                   tau_win: jnp.ndarray, sigma: jnp.ndarray, *, loss, reg,
                   pkeys: tuple, rho: float = 1.0):
    """One fused primal-dual step on a single VMEM-resident window.

    A thin adapter: builds the windowed prox, then runs the canonical
    engine step through the window ``executor``.  The Pallas kernel
    (kernels/pd_step.py) runs exactly this function on its loaded
    window, so interpret-mode kernel output is bit-comparable to the jnp
    reference (:func:`fused_pd_step_ref`).

    Precision policy: ``w_win`` / ``u_win`` and the prox parameter
    windows may arrive in a reduced *storage* dtype (bf16) — HBM<->VMEM
    traffic then moves half the bytes — while the contractions, prox
    solves, and dual resolvent always *accumulate* in f32: the window is
    upcast on entry and the outputs are cast back to the storage dtype.
    f32 storage is the identity path (bitwise unchanged).

    Window shapes (see ``core.graph.EdgeBlockLayout``): ``w_win`` (NW, n),
    ``u_win`` (EW, n), ``params_win`` a tuple of per-node prox parameter
    windows (leaves (NW, ...), keyed by the static ``pkeys`` — the
    sorted keys of ``loss.prox_setup``), ``tau_win`` (NW, 1), and per
    *owned* edge ``sigma`` (EB, 1); the executor carries the pre-scaled
    clip levels ``lam * A_e`` (the canonical step runs at ``lam = 1``).
    Returns (w_relaxed_window (NW, n), u_new_owned (EB, n)) in the
    storage dtype.
    """
    store = w_win.dtype
    f32 = jnp.float32
    params = dict(zip(
        pkeys,
        (p.astype(f32) if jnp.issubdtype(p.dtype, jnp.floating) else p
         for p in params_win)))

    def prox(v):
        return loss.prox_apply(params, v)

    w_new, u_new = _engine_pd_step(executor, prox, reg, 1.0, tau_win,
                                   sigma, w_win.astype(f32),
                                   u_win.astype(f32), rho=rho)
    return w_new.astype(store), u_new.astype(store)


def window_residual(w_old: jnp.ndarray, u_old: jnp.ndarray,
                    w_new: jnp.ndarray, u_new: jnp.ndarray,
                    tau_owned: jnp.ndarray, sigma: jnp.ndarray):
    """eq.-11 block residual over one window's *owned* rows (f32).

    The in-kernel statement of :func:`repro.engine.step.pd_residual` for
    a VMEM window: callers pass the owned node rows (BV, n) before/after
    and the owned dual rows (EB, n) before/after, with ``tau_owned``
    (BV, 1) / ``sigma`` (EB, 1).  Always accumulates in f32 so bf16
    storage runs report an honest residual.  Layout padding rows are
    inert (their state never moves), so they contribute 0.
    """
    f32 = jnp.float32
    rp = jnp.max(jnp.abs(w_new.astype(f32) - w_old.astype(f32))
                 / tau_owned.astype(f32))
    rd = jnp.max(jnp.abs(u_new.astype(f32) - u_old.astype(f32))
                 / sigma.astype(f32))
    return jnp.maximum(rp, rd)


def fused_pd_step_ref(w_store: jnp.ndarray, u_store: jnp.ndarray,
                      ends: jnp.ndarray, params: tuple, tau: jnp.ndarray,
                      sigma: jnp.ndarray, la: jnp.ndarray, *, loss, reg,
                      pkeys: tuple, block_nodes: int, block_edges: int,
                      kn: int, klo: int, khi: int, rho: float = 1.0,
                      iters: int = 1, compute_residual: bool = False):
    """jnp oracle for the fused PD kernel: vmap of the window step.

    Storage shapes (layout order, see ``EdgeBlockLayout``):
      w_store (nb*BV + (kn-1)*BV, n), u_store ((nb+klo+khi)*EB, n),
      ``ends`` ((nb+klo+khi)*EB, 2) int32 layout node ids (src, dst) of
      the edge in each u_store row (``core.graph.edge_ends_store``),
      tau and every ``params`` leaf padded to the w_store rows,
      sigma/la (nb*EB, 1) per owned edge.
    Returns (w_new (nb*BV, n), u_new (nb*EB, n)).  ``iters > 1`` (the
    whole-graph-in-VMEM multi-iteration fusion) requires nb == 1.

    With ``compute_residual`` the return gains a third element: the f32
    scalar eq.-11 residual of the call (max :func:`window_residual` over
    blocks; for ``iters > 1`` the running max over iterations), matching
    what the Pallas kernel accumulates in-kernel.

    Precision: on the ``iters > 1`` path the loop carry runs in f32 and
    the storage dtype is applied once at the end — bf16 is the *HBM*
    storage policy, and this path models a kernel whose carry never
    leaves VMEM (one storage-rounded write-back per launch).  The
    ``nb > 1`` grid path stores every iteration's output, so there the
    rounding is per iteration by construction.
    """
    bv, eb = block_nodes, block_edges
    nb = sigma.shape[0] // eb
    if iters != 1 and nb != 1:
        raise ValueError("multi-iteration fusion requires a single block")
    n = w_store.shape[1]
    nw, ew = kn * bv, (klo + 1 + khi) * eb
    def node_slice(a, n0):
        return jax.lax.dynamic_slice(
            a, (n0,) + (0,) * (a.ndim - 1), (nw,) + a.shape[1:])

    def block(i):
        n0, e0 = i * bv, i * eb
        w_win = jax.lax.dynamic_slice(w_store, (n0, 0), (nw, n))
        u_win = jax.lax.dynamic_slice(u_store, (e0, 0), (ew, n))
        # prox parameters are read-only across iterations: upcast a bf16
        # store once here instead of per pd_window_step call (the cast
        # inside is then a no-op) — identical values, ~params/state fewer
        # casts per fused iteration
        params_win = tuple(
            a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a
            for a in (node_slice(a, n0) for a in params))
        tau_win = jax.lax.dynamic_slice(tau, (n0, 0), (nw, 1))
        sg = jax.lax.dynamic_slice(sigma, (e0, 0), (eb, 1))
        bd = jax.lax.dynamic_slice(la, (e0, 0), (eb, 1))
        ex = window_executor(
            jax.lax.dynamic_slice(ends, (e0, 0), (ew, 2)), nw, n0, bd,
            klo=klo, block_edges=eb)

        def one(w_win_, u_win_):
            return pd_window_step(ex, w_win_, u_win_, params_win, tau_win,
                                  sg, loss=loss, reg=reg, pkeys=pkeys,
                                  rho=rho)

        u_owned_lo = klo * eb
        if iters == 1:
            w_o, u_o = one(w_win, u_win)
            if compute_residual:
                res = window_residual(
                    w_win[:bv],
                    jax.lax.dynamic_slice(u_win, (u_owned_lo, 0), (eb, n)),
                    w_o[:bv], u_o, tau_win[:bv], sg)
                return w_o[:bv], u_o, res
        else:
            # nb == 1: the window is the whole graph, so the relaxed
            # window output feeds straight back in (VMEM-resident loop).
            # bf16 is the *HBM* storage dtype: the loop carry runs in
            # f32 (one upcast per launch, one storage-rounded
            # write-back), exactly as the kernel keeps its VMEM carry
            store = w_win.dtype
            w_c, u_c = (w_win.astype(jnp.float32),
                        u_win.astype(jnp.float32))
            if compute_residual:
                def body(_, c):
                    w_, u_, r_ = c
                    w_n, u_n = one(w_, u_)
                    r_n = window_residual(w_[:bv], u_, w_n[:bv], u_n,
                                          tau_win[:bv], sg)
                    # kn == 1 here, so the owned dual rows are the window
                    return w_n, u_n, jnp.maximum(r_, r_n)
                w_o, u_o, res = jax.lax.fori_loop(
                    0, iters, body, (w_c, u_c, jnp.float32(0.0)))
                return w_o[:bv].astype(store), u_o.astype(store), res
            w_o, u_o = jax.lax.fori_loop(
                0, iters, lambda _, c: one(*c), (w_c, u_c))
            w_o, u_o = w_o.astype(store), u_o.astype(store)
        return w_o[:bv], u_o

    if nb == 1:
        # single whole-graph block: skip the vmap wrapper (a size-1 batch
        # axis defeats XLA gather fusion) — the slices fold away at i=0
        return block(0)
    if compute_residual:
        w_new, u_new, res = jax.vmap(block)(jnp.arange(nb))
        return (w_new.reshape(nb * bv, n), u_new.reshape(nb * eb, n),
                jnp.max(res))
    w_new, u_new = jax.vmap(block)(jnp.arange(nb))
    return w_new.reshape(nb * bv, n), u_new.reshape(nb * eb, n)
