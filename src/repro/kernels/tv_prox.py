"""Pallas TPU kernel: edge-wise dual clipping (Algorithm 1 step 10).

u^(e) <- T^(lambda A_e)(u^(e)) — a projection of each edge's dual vector
onto the box [-lambda A_e, +lambda A_e].  Purely element-wise over the
(E, n) dual signal; on TPU this is a VPU (vector unit) kernel tiled so each
grid step streams one (BLOCK_E, n) tile HBM -> VMEM -> HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_E = 512


def _tv_prox_kernel(u_ref, bound_ref, o_ref):
    u = u_ref[...]
    b = bound_ref[...]            # (BLOCK_E, 1) broadcast over features
    o_ref[...] = jnp.clip(u, -b, b)


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def tv_prox(u: jnp.ndarray, bound: jnp.ndarray, *,
            block_e: int = DEFAULT_BLOCK_E,
            interpret: bool = False) -> jnp.ndarray:
    """Clip each row of u (E, n) to [-bound_e, +bound_e].

    bound: (E,).  E is padded to a multiple of block_e.
    """
    e, n = u.shape
    e_pad = -(-e // block_e) * block_e
    if e_pad != e:
        u = jnp.pad(u, ((0, e_pad - e), (0, 0)))
        bound = jnp.pad(bound, (0, e_pad - e))
    b2 = bound[:, None].astype(u.dtype)

    out = pl.pallas_call(
        _tv_prox_kernel,
        grid=(e_pad // block_e,),
        in_specs=[
            pl.BlockSpec((block_e, n), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_e, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e_pad, n), u.dtype),
        interpret=interpret,
        name="tv_prox",
    )(u, b2)
    return out[:e]
