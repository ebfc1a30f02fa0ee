"""The four realizations of the engine's :class:`GraphExecutor` protocol.

Each executor answers one question — *how do D and D^T run on this
substrate?* — so :func:`repro.engine.step.pd_step` stays the only
statement of the iteration math:

  * :class:`DenseExecutor`    — padded incidence-table gather-sum on one
    device (the dense / unfused-pallas backends and every legacy shim),
  * :class:`WindowExecutor`   — a single VMEM-resident window of the
    edge-blocked layout; the fused Pallas kernel's in-kernel body runs
    the canonical step through this executor,
  * :class:`HaloExecutor`     — shard_map collectives over a device mesh
    (dense all-gather or boundary-only exchange),
  * :class:`MailboxExecutor`  — the federated runtime's per-edge message
    protocol: duals read through owner broadcasts, primal differences
    through persistent (optionally compressed) mailboxes.

Executors also stand in for the graph inside the regularizer resolvents:
``weights`` is the per-owned-edge A_e in the executor's own edge order.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.obs import profile as _prof
from repro.obs.profile import annotate as _scope


@dataclasses.dataclass(frozen=True)
class DenseExecutor:
    """Single-device executor over an :class:`EmpiricalGraph`."""

    graph: Any

    @property
    def weights(self) -> jnp.ndarray:
        return self.graph.weights

    def gather_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        return self.graph.incidence_transpose_apply(u)

    def edge_diff(self, z: jnp.ndarray) -> jnp.ndarray:
        return self.graph.incidence_apply(z)

    def owned_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        return u


def _bf16_parts(x: jnp.ndarray) -> tuple:
    """Three bf16 arrays whose f32 sum is exactly ``x`` (8 + 8 + 8 of
    f32's 24 significand bits)."""
    parts = []
    for _ in range(3):
        p = x.astype(jnp.bfloat16)
        parts.append(p)
        x = x - p.astype(jnp.float32)
    return tuple(parts)


def _signed_one_hot(src: jnp.ndarray, dst: jnp.ndarray, shape: tuple,
                    axis: int) -> jnp.ndarray:
    """bf16 ``shape`` matrix, +1 where the iota along ``axis`` equals
    ``src`` and -1 where it equals ``dst`` (both broadcast to ``shape``);
    bf16 holds 0/+-1 exactly."""
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    f32 = jnp.float32
    return ((idx == src).astype(f32) - (idx == dst).astype(f32)
            ).astype(jnp.bfloat16)


def _exact_dot(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """f32-exact contraction of a bf16-exact matrix ``a`` (entries 0/+-1)
    with an (rows, k) f32 ``x``: its three bf16 parts stacked on the lane
    axis into one (rows, 3k) right-hand side, so one single-pass bf16 MXU
    product with f32 accumulation gives all three partial products; they
    are summed part by part.  Each output column accumulates on its own,
    so the stacked product is bitwise three separate ones, at
    ceil(3k/128) MXU column tiles instead of 3 * ceil(k/128).  The TPU's
    default f32 matmul rounds ``x`` to bf16, and ``Precision.HIGHEST``
    spends six passes (and a far longer Mosaic compile) on what one
    exact stacked pass gives."""
    k = x.shape[1]
    y = jax.lax.dot_general(a, jnp.concatenate(_bf16_parts(x), axis=1),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y[:, :k] + y[:, k:2 * k] + y[:, 2 * k:]


@dataclasses.dataclass(frozen=True)
class WindowExecutor:
    """One VMEM window of the edge-blocked layout (``EdgeBlockLayout``).

    State shapes differ from the dense case: ``w`` is the (NW, n) node
    window (owned + halo blocks), the gather-side dual state is the
    (EW, n) edge window, and the executor *owns* the (EB, n) rows at
    offset ``klo * EB`` inside it.  ``weights`` carries the already
    lambda-scaled clip levels ``lam * A_e`` for the owned edges (the
    kernel precomputes them once per solve), so the canonical step is
    invoked with ``lam = 1.0``.

    D and D^T read the window-relative (src, dst) node ids ``ends`` of
    the (EW,) window edges.  Padding slots have src == dst and zero
    duals.  Endpoints outside the node window are dropped, so an edge
    crossing the window edge contributes only to its in-window endpoint;
    the layout guarantees every edge incident to an owned or halo node
    lies in the edge window, so those rows of D^T u are exact.

    Inside a compiled TPU kernel (``mxu=True``) Mosaic refuses row
    gathers and scatters by an index array, so both products there run
    as contractions with the window's signed incidence matrix (+1 at the
    src node, -1 at the dst node), built once per window from ``ends``
    with iota compares and held in bf16, which represents its 0/+-1
    entries exactly.  It is built in the orientation each product
    streams, so neither needs an in-kernel transpose of it: (NW, EW) for
    D^T u (from the transposed (2, EW) endpoint ids) and the owned
    (EB, NW) rows for D z.  Each product is one stacked bf16 MXU pass
    at f32 precision (:func:`_exact_dot`).  Everywhere else (the jnp
    reference and interpret mode, which run the same step) they are the
    O(EW) segment sum and row gather: the contraction costs O(EW * NW),
    which a CPU pays in full.

    Precision policy: the window adapter (``kernels.ref.pd_window_step``)
    upcasts a reduced-storage (bf16) window to f32 *before* calling the
    step, so every contraction here accumulates in f32 regardless of
    what dtype the state was stored in.
    """

    ends: jnp.ndarray           # (EW, 2) window-relative (src, dst) ids
    num_nodes: int              # NW
    weights: jnp.ndarray        # (EB, 1) lam * A_e per owned edge
    klo: int
    block_edges: int
    incidence_t: jnp.ndarray | None = None      # (NW, EW) bf16, mxu
    owned_incidence: jnp.ndarray | None = None  # (EB, NW) bf16, mxu

    @classmethod
    def from_endpoints(cls, ends: jnp.ndarray, num_nodes: int,
                       weights: jnp.ndarray, *, klo: int,
                       block_edges: int, mxu: bool = False
                       ) -> "WindowExecutor":
        """Build the executor from (EW, 2) window-relative (src, dst)
        node ids of the window's edges; ``mxu`` builds the two incidence
        matrices the contractions use."""
        incidence_t = owned_incidence = None
        if mxu:
            ends_t = ends.T                 # (2, EW): edge ids on lanes
            owned = jax.lax.slice_in_dim(ends, klo * block_edges,
                                         (klo + 1) * block_edges)
            incidence_t = _signed_one_hot(ends_t[0:1], ends_t[1:2],
                                          (num_nodes, ends.shape[0]), 0)
            owned_incidence = _signed_one_hot(
                owned[:, 0:1], owned[:, 1:2], (block_edges, num_nodes), 1)
        return cls(ends=ends, num_nodes=num_nodes, weights=weights,
                   klo=klo, block_edges=block_edges,
                   incidence_t=incidence_t,
                   owned_incidence=owned_incidence)

    def _owned_rows(self, a: jnp.ndarray) -> jnp.ndarray:
        eb = self.block_edges
        return jax.lax.slice_in_dim(a, self.klo * eb, (self.klo + 1) * eb)

    def gather_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        if self.incidence_t is not None:
            return _exact_dot(self.incidence_t, u)
        nw = self.num_nodes
        return (jax.ops.segment_sum(u, self.ends[:, 0], nw)
                - jax.ops.segment_sum(u, self.ends[:, 1], nw))

    def edge_diff(self, z: jnp.ndarray) -> jnp.ndarray:
        if self.owned_incidence is not None:
            return _exact_dot(self.owned_incidence, z)
        ends = self._owned_rows(self.ends)
        return z[ends[:, 0]] - z[ends[:, 1]]

    def owned_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        return self._owned_rows(u)


@dataclasses.dataclass(frozen=True)
class HaloExecutor:
    """shard_map executor: each shard owns ``vp`` nodes and the edges
    whose src endpoint it owns; D / D^T become lock-step collectives.

    ``comm`` selects the exchange (DESIGN.md §3.3): ``dense`` all-gathers
    the primal block and psums the dense D^T u accumulator; ``boundary``
    exchanges only rows marked in ``send`` (nodes touching cut edges).
    Built *inside* the shard_map body — ``base = shard_index * vp`` is a
    traced value.
    """

    axis: str
    comm: str
    vp: int
    v_pad: int
    base: Any                   # traced: this shard's first global row
    src: jnp.ndarray            # (ep,) global node ids of owned edges
    dst: jnp.ndarray
    weights: jnp.ndarray        # (ep,) A_e (0 for padded edge slots)
    send: jnp.ndarray           # (vp,) 1.0 if local node is boundary
    send_full: jnp.ndarray | None   # (V_pad,) boundary mask, boundary mode

    def gather_duals(self, u_loc: jnp.ndarray) -> jnp.ndarray:
        """All-shards-summed D^T u, returning the local (vp, n) block."""
        with _scope(_prof.PHASE_HALO_GATHER):
            vp, n = self.vp, u_loc.shape[1]
            acc = jnp.zeros((self.v_pad, n), u_loc.dtype)
            acc = acc.at[self.src].add(u_loc)
            acc = acc.at[self.dst].add(-u_loc)
            if self.comm == "dense":
                tot = jax.lax.psum(acc, self.axis)
            else:
                # shard-internal part stays local; only boundary rows
                # summed
                local_rows = jax.lax.dynamic_slice(acc, (self.base, 0),
                                                   (vp, n))
                bacc = acc * self.send_full[:, None]
                tot_b = jax.lax.psum(bacc, self.axis)
                tot = jax.lax.dynamic_update_slice(
                    jnp.zeros_like(acc), local_rows, (self.base, 0))
                # rows that are boundary take the global sum instead
                tot = jnp.where(self.send_full[:, None] > 0, tot_b, tot)
            return jax.lax.dynamic_slice(tot, (self.base, 0), (vp, n))

    def edge_diff(self, z_loc: jnp.ndarray) -> jnp.ndarray:
        with _scope(_prof.PHASE_HALO_DIFF):
            n = z_loc.shape[1]
            if self.comm == "dense":
                zg = jax.lax.all_gather(z_loc, self.axis, tiled=True)
            else:
                # boundary mode: exchange only rows marked in `send`;
                # local rows come from the local block, remote
                # non-boundary rows are never read (their edges are
                # shard-internal elsewhere).
                contrib = jnp.zeros((self.v_pad, n), z_loc.dtype)
                contrib = jax.lax.dynamic_update_slice(
                    contrib, z_loc * self.send[:, None], (self.base, 0))
                zg = jax.lax.psum(contrib, self.axis)
                # overwrite own block with exact local values
                zg = jax.lax.dynamic_update_slice(zg, z_loc,
                                                  (self.base, 0))
            return zg[self.src] - zg[self.dst]

    def owned_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        return u


@dataclasses.dataclass(frozen=True)
class HierarchicalExecutor:
    """Two-level executor: a fused edge-blocked kernel *inside* each
    shard_map shard, with a halo dual-refresh between shards.

    Unlike the other executors, D / D^T do not run here — the per-shard
    :func:`repro.kernels.ops.pd_step` launch runs them through a
    :class:`WindowExecutor` on the shard's local edge-blocked layout
    (``core.partition.HierarchyPlan``).  What crosses shards each
    iteration is a single ``all_gather`` of *owned dual* rows
    (``refresh_duals``): each shard's local subgraph is the 1-hop halo
    closure of its owned nodes, so refreshing the duals of replicated
    (non-owned) edges from their owners is the only communication the
    fused step needs to stay exact on owned state — halo-node primal
    updates are recomputed redundantly instead of exchanged, and the
    locally-computed duals of replicated edges are overwritten at the
    next refresh, so second-ring staleness never reaches owned rows.

    ``comm`` selects the exchange payload (DESIGN.md §3.3): ``boundary``
    gathers a compacted per-owner send list (NS rows/shard, NS = max
    replicated-edge demand), ``dense`` gathers the whole owned dual slab
    (NE rows/shard).  ``recv_src`` is pre-resolved for the chosen mode.
    Built inside the shard_map body; all index tables are the shard's
    slice of the stacked ``HierarchyPlan`` arrays.
    """

    axis: str
    comm: str
    num_blocks: int
    block_nodes: int
    block_edges: int
    klo: int
    # per-shard tables (shard_map-local slices)
    node_owned: jnp.ndarray     # (NV, 1) residual mask over layout nodes
    edge_owned: jnp.ndarray     # (NE, 1) 1.0 where this shard owns the edge
    orient: jnp.ndarray         # (NE, 1) u_layout = orient * u_global
    send_idx: jnp.ndarray       # (NS,) owned slots to publish (boundary)
    send_flip: jnp.ndarray      # (NS, 1) orientation at those slots
    recv_src: jnp.ndarray       # (NE,) row in the gathered buffer
    recv_flip: jnp.ndarray      # (NE, 1) receiver-side orientation

    @property
    def weights(self) -> jnp.ndarray:  # pragma: no cover - protocol stub
        raise NotImplementedError(
            "HierarchicalExecutor delegates the step to the fused kernel")

    def owned_duals(self, u_store: jnp.ndarray) -> jnp.ndarray:
        eb, nb = self.block_edges, self.num_blocks
        return jax.lax.dynamic_slice(
            u_store, (self.klo * eb, 0), (nb * eb, u_store.shape[1]))

    def refresh_duals(self, u_store: jnp.ndarray) -> jnp.ndarray:
        """Overwrite replicated dual slots with their owners' values.

        Publishes owned rows in *global* orientation, all-gathers across
        the mesh axis, and re-orients received rows into the local
        layout.  Owned slots and inert padding slots are left untouched
        (``recv_flip`` is 0 there, but the ``where`` keeps them exactly).
        """
        with _scope(_prof.PHASE_HALO_GATHER):
            u_own = self.owned_duals(u_store)
            if self.comm == "boundary":
                buf = u_own[self.send_idx] * self.send_flip
            else:
                buf = u_own * self.orient
            allbuf = jax.lax.all_gather(buf, self.axis, tiled=True)
            u_ref = jnp.where(self.edge_owned > 0, u_own,
                              allbuf[self.recv_src] * self.recv_flip)
            return jax.lax.dynamic_update_slice(
                u_store, u_ref, (self.klo * self.block_edges, 0))

    def write_back(self, w_store, u_store, w_new, u_new):
        """Store the fused step's owned-region outputs (halo padding rows
        of ``w_store`` are inert zeros and never rewritten)."""
        w_store = jax.lax.dynamic_update_slice(w_store, w_new, (0, 0))
        u_store = jax.lax.dynamic_update_slice(
            u_store, u_new, (self.klo * self.block_edges, 0))
        return w_store, u_store

    def residual(self, w_store, u_refreshed, w_new, u_new, tau, sigma):
        """Shard-local eq.-11 residual masked to *owned* rows.

        Owned rows see exactly the global update (halo closure), so the
        host max of these per-shard values equals the global residual;
        halo/ring rows are excluded because their local primal state is
        not the global one.
        """
        f32 = jnp.float32
        nv = self.num_blocks * self.block_nodes
        w_old = jax.lax.dynamic_slice(
            w_store, (0, 0), (nv, w_store.shape[1]))
        rp = jnp.max(self.node_owned
                     * jnp.abs(w_new.astype(f32) - w_old.astype(f32))
                     / tau[:nv].astype(f32))
        u_old = self.owned_duals(u_refreshed)
        rd = jnp.max(self.edge_owned
                     * jnp.abs(u_new.astype(f32) - u_old.astype(f32))
                     / sigma.astype(f32))
        return jnp.maximum(rp, rd)


class MailboxExecutor:
    """Federated message-passing executor (one communication round).

    Duals are gathered from owned rows plus the owner-broadcast mirrors
    ``u_recv`` (stale while the owner sleeps); the edge difference runs
    through the persistent primal mailboxes: active dst endpoints post a
    (compressed) copy of their operand ``z`` up to the edge owner, and
    the difference is formed against the mailbox content.  The refreshed
    mailbox state is left on ``z_recv_new`` for the round protocol to
    carry forward — an executor is built fresh each round.
    """

    def __init__(self, graph, u_recv, z_recv, pos_signs, active_dst,
                 compress: Callable):
        self.graph = graph
        self.u_recv = u_recv
        self.z_recv = z_recv
        self.pos_signs = pos_signs          # (V, max_deg, 1) owner-side mask
        self.active_dst = active_dst        # (E, 1) bool
        self.compress = compress
        self.z_recv_new = None

    @property
    def weights(self) -> jnp.ndarray:
        return self.graph.weights

    def gather_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        g = self.graph
        gathered = jnp.where(self.pos_signs, u[g.inc_edges],
                             self.u_recv[g.inc_edges])
        return jnp.einsum("vd,vdn->vn", g.inc_signs, gathered,
                          precision="highest")

    def edge_diff(self, z: jnp.ndarray) -> jnp.ndarray:
        with _scope(_prof.PHASE_MAILBOX_DIFF):
            g = self.graph
            self.z_recv_new = jnp.where(self.active_dst,
                                        self.compress(z[g.dst]),
                                        self.z_recv)
            return z[g.src] - self.z_recv_new

    def owned_duals(self, u: jnp.ndarray) -> jnp.ndarray:
        return u
