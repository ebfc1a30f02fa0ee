"""Pallas TPU kernel: batched node-wise ridge primal update (paper eq. 21).

w_i = P_i @ v_i for every node i, with P_i = (I + (2 tau_i/m_i) Q_i)^{-1}
precomputed at setup.  This is the compute hot-spot of the squared-loss
primal step: a (V, n, n) x (V, n) batched matvec.  The kernel tiles nodes
into BLOCK_V-sized groups; each grid step performs a (BLOCK_V, n, n) batch
of rank-1 MXU matmuls entirely in VMEM.

For MXU efficiency n should be padded to a lane multiple (128 on TPU;
the ops wrapper pads).  Validation runs with interpret=True on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_V = 256


def _ridge_kernel(p_ref, v_ref, o_ref):
    p = p_ref[...]                # (BLOCK_V, n, n)
    v = v_ref[...]                # (BLOCK_V, n)
    # batched matvec: contract the last axis of p with v
    o_ref[...] = jnp.einsum("bnk,bk->bn", p, v,
                            preferred_element_type=jnp.float32,
                            precision="highest").astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def batched_affine(p: jnp.ndarray, v: jnp.ndarray, *,
                   block_v: int = DEFAULT_BLOCK_V,
                   interpret: bool = False) -> jnp.ndarray:
    """w_i = P_i v_i batched over nodes. p: (V, n, n), v: (V, n)."""
    vcount, n = v.shape
    v_pad = -(-vcount // block_v) * block_v
    if v_pad != vcount:
        p = jnp.pad(p, ((0, v_pad - vcount), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, v_pad - vcount), (0, 0)))

    out = pl.pallas_call(
        _ridge_kernel,
        grid=(v_pad // block_v,),
        in_specs=[
            pl.BlockSpec((block_v, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_v, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v_pad, n), v.dtype),
        interpret=interpret,
        name="batched_affine",
    )(p, v)
    return out[:vcount]
