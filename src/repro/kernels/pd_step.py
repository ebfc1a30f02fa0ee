"""Pallas TPU kernel: fused primal-dual step (Algorithm 1, eqs. 14-15).

The unfused ``pallas`` backend realizes one primal-dual iteration as four
separate HBM round-trips (dense D^T u gather, prox, D apply, dual
resolvent).  This kernel fuses the whole step: the grid runs over *node
blocks* of an edge-blocked graph layout (``core.graph.EdgeBlockLayout``),
and each grid step keeps its node window ``w``, the incident dual rows
``u``, the loss's prox parameters and the dual step/clip parameters
VMEM-resident while it computes

    primal gather-sum D^T u  ->  loss prox (eq. 18)
    ->  D (2 w+ - w)         ->  regularizer dual resolvent (step 10)

emitting ``w+`` and ``u+`` with one HBM read and one write per tensor
(halo rows are re-read by neighbouring blocks; the four intermediate
edge/node signals never touch HBM).

The loss and regularizer are *static template slots*: the prox
parameters arrive as a tuple of per-node arrays (``loss.prox_setup``
leaves, sorted by key) each getting its own windowed BlockSpec, and the
in-kernel body is ``kernels.ref.pd_window_step`` — which itself runs the
canonical ``repro.engine.step.pd_step`` through a window executor.  The
iteration math is therefore stated once in the engine; this kernel is
locked to it by the interpret-mode bit-parity tests.  Compiled for a
TPU, D and D^T run as MXU contractions with the window's signed
incidence matrix, built from the window edges' endpoints by iota
compares in the orientation each contraction streams
(``engine.executors.WindowExecutor``): Mosaic refuses row gathers by an
index array, and the window is small enough to hold the matrix.  Each
product is one bf16 MXU pass over the three bf16 parts of its f32
operand stacked on the lane axis, exact to f32.  In interpret mode they
are the reference's segment sum and row gather.

Layout contract (all index maps are plain ``i + j`` offsets because the
layout pass aligns every block's halo window to exactly ``i * BV`` /
``i * EB`` in the padded storage — no scalar prefetch needed):

  * node storage rows:  ``nb*BV`` owned + ``(kn-1)*BV`` suffix padding,
  * edge storage rows:  ``klo*EB`` prefix + ``nb*EB`` owned + ``khi*EB``
    suffix padding; the (src, dst) endpoint store ``ends`` shares these
    rows (layout node ids; padding rows have src == dst),
  * per grid step ``i``: node window = ``kn`` consecutive BV-blocks from
    ``i``, edge window = ``klo+1+khi`` consecutive EB-blocks from ``i``.

When the whole graph fits one block (``nb == 1``), ``iters > 1`` runs a
``fori_loop`` *inside* the kernel — multi-iteration fusion with the
``(w, u)`` carry never leaving VMEM.  The carry accumulates in f32
regardless of the storage dtype: bf16 is the HBM storage policy, so a
reduced-precision round happens once per launch (the single write-back),
mirroring the one-HBM-round-trip-per-iteration rounding of the
multi-block grid path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import fused_vmem_cap
from repro.kernels import ref as _ref


def _make_kernel(bv: int, eb: int, kn: int, ktot: int, klo: int,
                 num_params: int, loss, reg, pkeys: tuple, rho: float,
                 iters: int, compute_residual: bool, mxu: bool):
    """Build the grid-step kernel for fixed layout extents."""

    def cat(refs):
        if len(refs) == 1:
            return refs[0][...]
        return jnp.concatenate([r[...] for r in refs], axis=0)

    def kernel(*refs):
        pos = 0
        w_refs = refs[pos:pos + kn]; pos += kn
        u_refs = refs[pos:pos + ktot]; pos += ktot
        end_refs = refs[pos:pos + ktot]; pos += ktot
        param_refs = [refs[pos + p * kn:pos + (p + 1) * kn]
                      for p in range(num_params)]
        pos += num_params * kn
        tau_refs = refs[pos:pos + kn]; pos += kn
        sig_ref, la_ref = refs[pos:pos + 2]; pos += 2
        w_out_ref, u_out_ref = refs[pos:pos + 2]; pos += 2
        res_ref = refs[pos] if compute_residual else None

        i = pl.program_id(0)
        w_win = cat(w_refs)                      # (NW, n)
        u_win = cat(u_refs)                      # (EW, n)
        params_win = tuple(cat(prefs) for prefs in param_refs)
        tau_win = cat(tau_refs)
        sg = sig_ref[...]
        ex = _ref.window_executor(cat(end_refs), w_win.shape[0], i * bv,
                                  la_ref[...], klo=klo, block_edges=eb,
                                  mxu=mxu)

        def one(w, u):
            return _ref.pd_window_step(ex, w, u, params_win, tau_win, sg,
                                       loss=loss, reg=reg, pkeys=pkeys,
                                       rho=rho)

        if iters == 1:
            w_o, u_o = one(w_win, u_win)
            w_out_ref[...] = w_o[:bv]
            u_out_ref[...] = u_o
            if compute_residual:
                # owned dual rows sit at window offset klo*EB
                u_owned = u_win[klo * eb:(klo + 1) * eb]
                res_ref[...] = _ref.window_residual(
                    w_win[:bv], u_owned, w_o[:bv], u_o, tau_win[:bv],
                    sg).reshape(1, 1)
        elif compute_residual:
            # single-block fusion with the eq.-11 residual accumulated
            # in-kernel: the running max over iterations rides the VMEM
            # carry, so a tol solve reads back one scalar per launch.
            # bf16 is the *HBM* storage dtype — the VMEM-resident carry
            # accumulates in f32 (upcast once per launch, downcast on
            # the single write-back), matching the per-launch rounding
            # of the multi-block grid path's one HBM round-trip.
            def body(_, c):
                w_, u_, r_ = c
                w_n, u_n = one(w_, u_)
                r_n = _ref.window_residual(w_[:bv], u_, w_n[:bv], u_n,
                                           tau_win[:bv], sg)
                return w_n, u_n, jnp.maximum(r_, r_n)
            w_o, u_o, res = jax.lax.fori_loop(
                0, iters, body, (w_win.astype(jnp.float32),
                                 u_win.astype(jnp.float32),
                                 jnp.float32(0.0)))
            w_out_ref[...] = w_o.astype(w_win.dtype)
            u_out_ref[...] = u_o.astype(u_win.dtype)
            res_ref[...] = res.reshape(1, 1)
        else:
            # single-block multi-iteration fusion: carry stays in VMEM,
            # in f32 (see above); storage rounding once per launch
            w_o, u_o = jax.lax.fori_loop(
                0, iters, lambda _, c: one(*c),
                (w_win.astype(jnp.float32), u_win.astype(jnp.float32)))
            w_out_ref[...] = w_o.astype(w_win.dtype)
            u_out_ref[...] = u_o.astype(u_win.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "loss", "reg", "pkeys", "block_nodes", "block_edges", "kn", "klo",
    "khi", "rho", "iters", "compute_residual", "interpret"))
def fused_pd_step(w_store: jnp.ndarray, u_store: jnp.ndarray,
                  ends: jnp.ndarray, params: tuple, tau: jnp.ndarray,
                  sigma: jnp.ndarray, la: jnp.ndarray, *, loss, reg,
                  pkeys: tuple, block_nodes: int, block_edges: int,
                  kn: int, klo: int, khi: int, rho: float = 1.0,
                  iters: int = 1, compute_residual: bool = False,
                  interpret: bool = False):
    """Fused PD step over the edge-blocked layout (storage shapes as
    ``kernels.ref.fused_pd_step_ref``).  Returns (w_new (nb*BV, n),
    u_new (nb*EB, n)); with ``compute_residual`` also the f32 scalar
    eq.-11 residual of the call (max over blocks, and over iterations
    when ``iters > 1``), computed in-kernel so a tol solve never reads
    the state back to form its stopping criterion.

    The kernel may claim the fused VMEM cap
    (:func:`repro.core.graph.fused_vmem_cap`); the routers only hand it
    layouts whose window estimate fits under it
    (``EdgeBlockLayout.window_bytes``)."""
    bv, eb = block_nodes, block_edges
    ktot = klo + 1 + khi
    nb = sigma.shape[0] // eb
    if iters != 1 and nb != 1:
        raise ValueError("multi-iteration fusion requires a single block")
    n = w_store.shape[1]
    params = tuple(params)

    def nmap(j, rank=2):
        return lambda i, j=j: (i + j,) + (0,) * (rank - 1)

    param_specs = [
        pl.BlockSpec((bv,) + leaf.shape[1:], nmap(j, leaf.ndim))
        for leaf in params for j in range(kn)
    ]
    in_specs = (
        [pl.BlockSpec((bv, n), nmap(j)) for j in range(kn)]          # w views
        + [pl.BlockSpec((eb, n), nmap(j)) for j in range(ktot)]      # u views
        + [pl.BlockSpec((eb, 2), nmap(j)) for j in range(ktot)]      # ends
        + param_specs                                                # prox
        + [pl.BlockSpec((bv, 1), nmap(j)) for j in range(kn)]        # tau
        + [pl.BlockSpec((eb, 1), nmap(0))] * 2                       # sig/la
    )
    out_specs = [pl.BlockSpec((bv, n), nmap(0)),
                 pl.BlockSpec((eb, n), nmap(0))]
    out_shape = [jax.ShapeDtypeStruct((nb * bv, n), w_store.dtype),
                 jax.ShapeDtypeStruct((nb * eb, n), u_store.dtype)]
    if compute_residual:
        # one (1, 1) tile per grid step: the block's minor dims equal the
        # array's, which is what the TPU lowering requires of them
        out_specs.append(pl.BlockSpec((None, 1, 1), nmap(0, 3)))
        out_shape.append(jax.ShapeDtypeStruct((nb, 1, 1), jnp.float32))

    operands = (
        [w_store] * kn + [u_store] * ktot + [ends] * ktot
        + [leaf for leaf in params for _ in range(kn)]
        + [tau] * kn + [sigma, la]
    )
    outs = pl.pallas_call(
        _make_kernel(bv, eb, kn, ktot, klo, len(params), loss, reg,
                     pkeys, rho, iters, compute_residual,
                     mxu=not interpret),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=fused_vmem_cap()),
        interpret=interpret,
        name="fused_pd_step",
    )(*operands)
    if compute_residual:
        w_new, u_new, res = outs
        return w_new, u_new, jnp.max(res)
    w_new, u_new = outs
    return w_new, u_new
