"""Plain reference of Algorithm 1 for the network Lasso with the squared
loss and total variation (arXiv:2010.14159, eqs. 4, 14-15, 21).

It imports nothing of the program and takes nothing the program made:
the graph and the data are the benchmark's own arrays.  Per node i,
tau_i = 1 / deg(i) (1 for an isolated node) and per edge sigma = 1/2.
One iteration from (w, u), with D the signed incidence ((D w)_e =
w_src - w_dst) and A_e the edge weights:

    w_h = PU(w - tau D^T u)                 PU_i(v) = argmin_z
                                            L_i(z) + |v - z|^2/(2 tau_i)
    u_h = clip(u + sigma D (2 w_h - w), +-lam A_e)
    w+  = w + rho (w_h - w)
    u+  = clip(u + rho (u_h - u), +-lam A_e)

and its eq.-11 residual is max(max |w+ - w| / tau, max |u+ - u| / sigma).
The prox parameters are formed on the host in float64; the iterations
run in float32 (the precision the configurations state) with every
product at full precision, or, for the control, with the state stored
in ``dtype`` between iterations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SIGMA = 0.5


class Reference:
    """Algorithm 1 on one deployment's graph at one lambda."""

    def __init__(self, edges, weights, num_nodes: int, lam: float,
                 rho: float):
        edges = np.asarray(edges, np.int64)
        self.num_nodes = int(num_nodes)
        self.src = edges[:, 0]
        self.dst = edges[:, 1]
        self.weights = np.asarray(weights, np.float64)
        self.lam = float(lam)
        self.rho = float(rho)
        deg = (np.bincount(self.src, minlength=num_nodes)
               + np.bincount(self.dst, minlength=num_nodes))
        self.tau = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 1.0)
        self._dev = None

    def _device_graph(self):
        if self._dev is None:
            self._dev = (jnp.asarray(self.src, jnp.int32),
                         jnp.asarray(self.dst, jnp.int32),
                         jnp.asarray(self.tau, jnp.float32),
                         jnp.asarray(self.lam * self.weights, jnp.float32))
        return self._dev

    # -- the primal update, eq. 21 ------------------------------------------
    def prox_params(self, x, y, labeled):
        """PU_i(v) = P_i (v + b_i) with P_i = (I + c_i X_i^T X_i)^-1,
        b_i = c_i X_i^T y_i, c_i = 2 tau_i / m_i; identity at unlabeled
        nodes.  Formed in float64, returned as float32 device arrays."""
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        m, n = x.shape[1], x.shape[2]
        c = 2.0 * self.tau / m
        q = np.einsum("vmn,vmk->vnk", x, x)
        a = np.eye(n)[None] + c[:, None, None] * q
        p = np.linalg.inv(a)
        b = c[:, None] * np.einsum("vmn,vm->vn", x, y)
        lab = np.asarray(labeled) > 0
        p[~lab] = np.eye(n)
        b[~lab] = 0.0
        return jnp.asarray(p, jnp.float32), jnp.asarray(b, jnp.float32)

    # -- iterations on the device -------------------------------------------
    def run(self, params, w0, u0, iters: int, last_block: int = 1,
            dtype: str = "float32"):
        """``iters`` iterations from (w0, u0); returns (w, u, the largest
        residual of the last ``last_block`` iterations)."""
        src, dst, tau, la = self._device_graph()
        p, b = params
        return _run(src, dst, tau, la, p, b,
                    jnp.asarray(w0, jnp.float32),
                    jnp.asarray(u0, jnp.float32), self.rho,
                    iters=int(iters), last_block=int(last_block),
                    num_nodes=self.num_nodes, dtype=dtype)

    def residual(self, params, w, u) -> float:
        """The eq.-11 residual of one iteration from (w, u)."""
        return float(self.run(params, w, u, 1)[2])

    def solve(self, params, w0, u0, *, tol: float, metric_every: int,
              budget: int, dtype: str = "float32"):
        """Iterate in blocks of ``metric_every`` until a block's largest
        residual is at or below ``tol`` or ``budget`` is spent; returns
        (w, u, that residual, iterations)."""
        w, u, its = w0, u0, 0
        while True:
            w, u, res = self.run(params, w, u, metric_every,
                                 last_block=metric_every, dtype=dtype)
            its += metric_every
            if float(res) <= tol or its >= budget:
                return w, u, float(res), its


@functools.partial(jax.jit, static_argnames=("iters", "last_block",
                                             "num_nodes", "dtype"))
def _run(src, dst, tau, la, p, b, w, u, rho, *, iters: int,
         last_block: int, num_nodes: int, dtype: str):
    store = jnp.dtype(dtype)
    hi = jax.lax.Precision.HIGHEST
    p = p.astype(store).astype(jnp.float32)
    b = b.astype(store).astype(jnp.float32)
    tau_c = tau[:, None]
    la_c = la[:, None]

    def step(w, u):
        dtu = (jax.ops.segment_sum(u, src, num_segments=num_nodes)
               - jax.ops.segment_sum(u, dst, num_segments=num_nodes))
        w_h = jnp.einsum("vnk,vk->vn", p, w - tau_c * dtu + b,
                         precision=hi)
        z = 2.0 * w_h - w
        u_h = jnp.clip(u + SIGMA * (z[src] - z[dst]), -la_c, la_c)
        w_new = w + rho * (w_h - w)
        u_new = jnp.clip(u + rho * (u_h - u), -la_c, la_c)
        # the state as it is stored between iterations
        w_new = w_new.astype(store).astype(jnp.float32)
        u_new = u_new.astype(store).astype(jnp.float32)
        res = jnp.maximum(jnp.max(jnp.abs(w_new - w) / tau_c),
                          jnp.max(jnp.abs(u_new - u)) / SIGMA)
        return w_new, u_new, res

    def body(i, carry):
        w, u, worst = carry
        w, u, res = step(w, u)
        worst = jnp.where(i >= iters - last_block,
                          jnp.maximum(worst, res), worst)
        return w, u, worst

    w = w.astype(store).astype(jnp.float32)
    u = u.astype(store).astype(jnp.float32)
    return jax.lax.fori_loop(0, iters, body, (w, u, jnp.float32(0.0)))
