"""Empirical graph of local datasets (paper §2, Fig. 1).

The empirical graph G = (V, E, A) relates local datasets: node i holds a
local dataset X^(i); an undirected edge {i, j} with weight A_ij > 0 connects
statistically similar datasets.

TPU-native layout (DESIGN.md §3.1): instead of a CPU-style sparse CSR
scatter structure we keep

  * edge endpoint arrays ``src``/``dst`` of shape (|E|,) with src < dst
    (the paper's block-incidence convention: D_{e,i} = +I for e={i,j}, j>i,
    D_{e,j} = -I), and
  * a padded per-node incident-edge table ``inc_edges`` of shape
    (|V|, max_deg) with a matching sign table ``inc_signs`` (+1 / -1 / 0 for
    padding), so that D^T u is a dense masked gather-sum.

Both D and D^T applications are dense, vectorized, and shard cleanly over a
"data" mesh axis.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# structure hashes are content hashes of frozen arrays, so they are
# computed once per graph *object* (EmpiricalGraph hashes by identity);
# the weak cache never retains graphs
_STRUCT_HASH_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


@dataclasses.dataclass(frozen=True, eq=False)
class EdgeBlockLayout:
    """Edge-blocked graph layout for the fused primal-dual kernel.

    Precomputed on the host (``plan_edge_blocks``) and carried as *static*
    aux data on :class:`EmpiricalGraph` (``eq=False`` keeps the dataclass
    identity-hashable, so it rides through ``jax.jit`` as a static arg).

    Nodes are RCM-reordered and grouped into ``num_blocks`` blocks of
    ``block_nodes``; edges are relabeled, canonicalized (src < dst in the
    *new* numbering — ``edge_flip`` records orientation changes so dual
    variables transform correctly), sorted by src, and assigned to the
    block owning their src endpoint.  Each block then owns a contiguous,
    padded range of ``block_edges`` dual rows, and the layout guarantees:

      * every dst ("halo") endpoint of an edge owned by block b lies in
        the node window  [b*BV, b*BV + kn*BV),
      * every edge incident to an owned or halo node of block b lies in
        the edge window  [b*EB, b*EB + (klo+1+khi)*EB)  of the *shifted*
        edge storage (owned position + klo*EB),

    so the fused kernel's grid step b can keep the whole window VMEM
    resident and compute primal + dual updates with plain relative
    indexing (window starts are exactly b*BV / b*EB — no scalar prefetch).

    Attributes (arrays are jnp; layout-order unless noted):
      block_nodes/num_blocks/block_edges: BV, nb, EB above.
      kn, klo, khi:  halo window extents, in blocks.
      node_perm:     (nb*BV,) layout pos -> original node id (-1 padding).
      node_inv:      (V,) original node id -> layout pos.
      src, dst:      (nb*EB,) int32 endpoints in layout node ids (0 pads).
      weights:       (nb*EB,) float32 A_e (0.0 for padding slots).
      edge_pos:      (E,) original edge id -> owned layout position.
      edge_flip:     (E,) +1/-1; u_layout = edge_flip * u_original.
    """

    block_nodes: int
    num_blocks: int
    block_edges: int
    kn: int
    klo: int
    khi: int
    num_nodes: int
    num_edges: int
    node_perm: jnp.ndarray
    node_inv: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray
    weights: jnp.ndarray
    edge_pos: jnp.ndarray
    edge_flip: jnp.ndarray

    @property
    def nodes_pad(self) -> int:
        return self.num_blocks * self.block_nodes

    @property
    def edges_pad(self) -> int:
        return self.num_blocks * self.block_edges

    def pad_node_store(self, a: jnp.ndarray) -> jnp.ndarray:
        """Append the (kn-1)*BV halo-suffix padding rows to a
        (nodes_pad, ...) node-aligned array — the one store-shape
        convention shared by the fused scan/chunk/setup paths."""
        ext = (self.kn - 1) * self.block_nodes
        return jnp.pad(a, ((0, ext),) + ((0, 0),) * (a.ndim - 1))

    def window_bytes(self, num_features: int,
                     param_floats: int | None = None,
                     itemsize: int = 4) -> int:
        """VMEM one grid step of the fused kernel needs (see
        :func:`fused_window_bytes`)."""
        return fused_window_bytes(
            self.block_nodes, self.block_edges, self.kn, self.klo,
            self.khi, num_features, param_floats=param_floats,
            itemsize=itemsize)


# TPU VMEM holds an array in (8, 128) tiles over its two minor axes
_SUBLANES, _LANES = 8, 128
# live copies the v5e compiler keeps of the fused kernel's in-kernel
# values (the bf16 incidence matrices, the f32 edge-window and
# node-window values) and fixed scratch, fitted to the smallest
# vmem_limit_bytes it accepts (bisected to 0.1 MiB on compiles for a
# described v5e, with the incidence built in both streamed
# orientations; the §5 SBM row, one block, holds both whole):
#   layout (BV, EB, kn, klo, khi)   loss       accepted   estimate
#   512x512 lattice (256,512,3,2,2) squared    15.0 MiB   36.4 MiB
#   512x512 lattice (512,1024,2,1,1) squared   20.7 MiB   52.2 MiB
#   512x512 lattice (256,512,3,2,2) logistic   48.9 MiB   76.9 MiB
#   §5 SBM (304,11072,1,0,0), 10 iters squared 63.7 MiB   69.0 MiB
#   1024^2 lattice / 4 shards (256,512,4,3,3)  19.6 MiB   58.2 MiB
_INC_COPIES, _EDGE_COPIES = 5, 4
_VMEM_SLACK = 2 << 20
# per-TensorCore VMEM of a TPU v5e, the chip this repository targets
_V5E_VMEM_BYTES = 128 << 20


def vmem_words(*shapes: tuple) -> int:
    """f32 words one node's (or edge's) values of the given per-row
    ``shapes`` take in VMEM: the last axis padded to 128 lanes, the one
    before it (if any) to 8 sublanes."""
    words = 0
    for shape in shapes:
        shape = tuple(shape) or (1,)
        lead = int(np.prod(shape[:-2], dtype=np.int64))
        sub = _round_up(shape[-2], _SUBLANES) if len(shape) > 1 else 1
        words += lead * sub * _round_up(shape[-1], _LANES)
    return words


def fused_window_bytes(block_nodes: int, block_edges: int, kn: int,
                       klo: int, khi: int, num_features: int, *,
                       param_floats: int | None = None,
                       itemsize: int = 4) -> int:
    """VMEM one grid step of the fused primal-dual kernel needs on a TPU.

    Two parts.  The in-kernel values: the window's bf16 signed
    incidence, counted as one (EW, NW) matrix, the f32 edge-window state
    (a 2-wide feature axis fills a 128-lane row) and the f32 node-window
    state with the prox parameters, times the live copies the compiler
    keeps (fitted; see the table above).  And the streamed blocks,
    double-buffered: ``itemsize`` is the *storage* dtype's width (4 for
    f32, 2 for bf16) and scales the state and prox-parameter blocks,
    while the endpoint and step operands stay 4-byte.

    ``param_floats`` is the per-node word count of the loss's prox
    parameters and in-kernel temporaries, tiled
    (``Loss.prox_param_floats``); it defaults to the squared loss's
    affine map (P, b).
    """
    bv, eb, n = block_nodes, block_edges, num_features
    ktot = klo + 1 + khi
    nw, ew = kn * bv, ktot * eb
    if param_floats is None:
        param_floats = vmem_words((n, n), (n,))
    row = vmem_words((n,))
    incidence = ew * _round_up(nw, _LANES) * 2
    values = (_INC_COPIES * incidence + _EDGE_COPIES * ew * row * 4
              + nw * (row + param_floats) * 4)
    state = nw * param_floats + (nw + bv + ew + eb) * n
    index = 2 * ew + nw + 2 * eb
    return values + 2 * (itemsize * state + 4 * index) + _VMEM_SLACK


def fused_vmem_cap() -> int:
    """VMEM limit the fused kernel requests: 3/4 of the TensorCore's, the
    rest left to the compiler (the v5e figure when no TPU is attached,
    as when compiling for a described one)."""
    cap = _V5E_VMEM_BYTES
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas import tpu as pltpu
        cap = pltpu.get_tpu_info().vmem_capacity_bytes
    return cap * 3 // 4


def fused_window_cap() -> int:
    """Largest fused window the routers accept: ``fused_vmem_cap()`` on
    a TPU, where the kernel is compiled, and uncapped elsewhere (the jnp
    reference and interpret mode hold no VMEM).
    ``REPRO_FUSED_MAX_WINDOW_BYTES`` overrides both."""
    env = os.environ.get("REPRO_FUSED_MAX_WINDOW_BYTES")
    if env:
        return int(env)
    return fused_vmem_cap() if jax.default_backend() == "tpu" else 1 << 62


def edge_ends_store(src, dst, klo: int, khi: int, block_edges: int):
    """(src, dst) layout node ids per *edge storage* row — the fused
    kernel's edge-window endpoint operand: owned-slot endpoints with the
    dual store's ``klo*EB`` prefix / ``khi*EB`` suffix rows added as
    (0, 0), an empty edge."""
    ends = jnp.stack([jnp.asarray(src, jnp.int32).reshape(-1),
                      jnp.asarray(dst, jnp.int32).reshape(-1)], axis=1)
    return jnp.pad(ends, ((klo * block_edges, khi * block_edges), (0, 0)))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class EmpiricalGraph:
    """Undirected empirical graph with dense padded incidence structure.

    Attributes:
      src, dst:   (E,) int32, endpoints of each edge, src[e] < dst[e].
      weights:    (E,) float32, similarity weights A_e > 0.
      inc_edges:  (V, max_deg) int32, edge ids incident to each node
                  (padded with 0; validity given by inc_signs != 0).
      inc_signs:  (V, max_deg) float32, +1 if node is the src (j > i side),
                  -1 if dst, 0 for padding.  Matches D_{e,i} blocks.
      num_nodes:  static int.
      layout:     optional :class:`EdgeBlockLayout` (static aux; attach
                  with :meth:`with_layout` to pre-plan the fused kernel's
                  edge-blocked layout once per graph).
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    weights: jnp.ndarray
    inc_edges: jnp.ndarray
    inc_signs: jnp.ndarray
    num_nodes: int
    layout: EdgeBlockLayout | None = None

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        children = (self.src, self.dst, self.weights, self.inc_edges,
                    self.inc_signs)
        return children, (self.num_nodes, self.layout)

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, weights, inc_edges, inc_signs = children
        num_nodes, layout = aux if isinstance(aux, tuple) else (aux, None)
        return cls(src, dst, weights, inc_edges, inc_signs, num_nodes,
                   layout)

    def with_layout(self, block_nodes: int | None = None) -> "EmpiricalGraph":
        """Attach a precomputed edge-blocked layout (host-side pass)."""
        return dataclasses.replace(
            self, layout=plan_edge_blocks(self, block_nodes=block_nodes))

    # -- basic properties ---------------------------------------------------
    @property
    def num_edges(self) -> int:
        return self.src.shape[0]

    @property
    def max_degree(self) -> int:
        return self.inc_edges.shape[1]

    def degrees(self) -> jnp.ndarray:
        """(V,) number of incident edges per node."""
        return jnp.sum(self.inc_signs != 0.0, axis=1)

    def structure_hash(self) -> str:
        """Canonical content hash of the graph structure.

        Hashes (num_nodes, src, dst, weights) — everything a solve plan
        (RCM order, edge-blocked layout, stepsizes) depends on, and
        nothing the node-local data contributes.  Two graphs built from
        the same edge set hash identically regardless of the input edge
        order (``build_graph`` canonicalizes), so a serving plan cache
        can key compiled layouts on it: same structure + different data
        shares a plan, any edge add/drop/reweight changes the hash.

        Computed once per graph object (content hashing pulls the edge
        arrays to the host) and memoized in a weak cache.
        """
        cached = _STRUCT_HASH_CACHE.get(self)
        if cached is None:
            h = hashlib.blake2b(digest_size=16)
            h.update(np.int64(self.num_nodes).tobytes())
            h.update(np.asarray(self.src, np.int64).tobytes())
            h.update(np.asarray(self.dst, np.int64).tobytes())
            h.update(np.asarray(self.weights, np.float32).tobytes())
            cached = h.hexdigest()
            _STRUCT_HASH_CACHE[self] = cached
        return cached

    # -- incidence operator D and its transpose -----------------------------
    def incidence_apply(self, w: jnp.ndarray) -> jnp.ndarray:
        """Apply block-incidence D: (V, n) node signal -> (E, n) edge signal.

        (D w)_e = w^(i) - w^(j) for e = {i, j}, i < j (paper's sign
        convention: +I on the smaller index).
        """
        return w[self.src] - w[self.dst]

    def incidence_transpose_apply(self, u: jnp.ndarray) -> jnp.ndarray:
        """Apply D^T: (E, n) edge signal -> (V, n) node signal.

        Uses the padded incidence table: dense masked gather-sum (no
        data-dependent scatter on TPU).
        """
        gathered = u[self.inc_edges]                     # (V, max_deg, n)
        return jnp.einsum("vd,vdn->vn", self.inc_signs, gathered,
                          precision="highest")

    # -- TV seminorm (paper eq. 3) ------------------------------------------
    def total_variation(self, w: jnp.ndarray) -> jnp.ndarray:
        """||w||_TV = sum_e A_e ||w^(i) - w^(j)||_1."""
        diffs = self.incidence_apply(w)
        return jnp.sum(self.weights * jnp.sum(jnp.abs(diffs), axis=1))

    # -- preconditioners (paper eq. 13) --------------------------------------
    def primal_stepsizes(self) -> jnp.ndarray:
        """tau_i = 1 / |N_i|  (nodes with no edges get tau = 1)."""
        deg = self.degrees().astype(jnp.float32)
        return jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 1.0)

    def dual_stepsizes(self) -> jnp.ndarray:
        """sigma_e = 1/2 for all edges."""
        return jnp.full((self.num_edges,), 0.5, dtype=jnp.float32)


def build_graph(edges: np.ndarray, weights: np.ndarray,
                num_nodes: int) -> EmpiricalGraph:
    """Build an EmpiricalGraph from an (E, 2) integer edge list.

    Edges are canonicalized to src < dst, deduplicated, and sorted.
    """
    edges = np.asarray(edges, dtype=np.int64)
    weights = np.asarray(weights, dtype=np.float32)
    if edges.size == 0:
        edges = np.zeros((0, 2), dtype=np.int64)
        weights = np.zeros((0,), dtype=np.float32)
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    if np.any(lo == hi):
        raise ValueError("self-loops are not allowed in the empirical graph")
    order = np.lexsort((hi, lo))
    lo, hi, weights = lo[order], hi[order], weights[order]
    # dedupe
    if len(lo):
        key = lo * num_nodes + hi
        keep = np.concatenate([[True], key[1:] != key[:-1]])
        lo, hi, weights = lo[keep], hi[keep], weights[keep]

    E = len(lo)
    deg = np.zeros(num_nodes, dtype=np.int64)
    np.add.at(deg, lo, 1)
    np.add.at(deg, hi, 1)
    max_deg = max(int(deg.max()) if num_nodes else 0, 1)

    # vectorized incidence scatter: interleave (src, dst) endpoints so each
    # node's slots keep edge order (src side +1 before dst side -1 for the
    # same edge), stable-sort by node, and the slot column is the rank
    # within the node's group — same fill order as a per-edge loop, O(E log E)
    inc_edges = np.zeros((num_nodes, max_deg), dtype=np.int32)
    inc_signs = np.zeros((num_nodes, max_deg), dtype=np.float32)
    if E:
        endpoints = np.empty(2 * E, dtype=np.int64)
        endpoints[0::2], endpoints[1::2] = lo, hi
        eid = np.repeat(np.arange(E, dtype=np.int64), 2)
        esign = np.tile(np.asarray([1.0, -1.0], np.float32), E)
        order2 = np.argsort(endpoints, kind="stable")
        nodes_sorted = endpoints[order2]
        group_start = np.concatenate([[0], np.cumsum(
            np.bincount(endpoints, minlength=num_nodes))])[:-1]
        slot = np.arange(2 * E) - group_start[nodes_sorted]
        inc_edges[nodes_sorted, slot] = eid[order2]
        inc_signs[nodes_sorted, slot] = esign[order2]

    return EmpiricalGraph(
        src=jnp.asarray(lo, jnp.int32),
        dst=jnp.asarray(hi, jnp.int32),
        weights=jnp.asarray(weights),
        inc_edges=jnp.asarray(inc_edges),
        inc_signs=jnp.asarray(inc_signs),
        num_nodes=int(num_nodes),
    )


def _round_up(x: int, mult: int) -> int:
    return -(-max(x, 1) // mult) * mult


def _plan_edge_blocks_fixed(graph: EmpiricalGraph, block_nodes: int,
                            min_extents: dict | None = None
                            ) -> EdgeBlockLayout:
    """Plan the edge-blocked layout for an explicit block size.

    ``min_extents`` forces lower bounds on the padded extents
    (``num_blocks`` / ``block_edges`` / ``kn`` / ``klo`` / ``khi``): the hierarchical partitioner plans every shard's
    local subgraph twice and re-plans with the across-shard maxima so all
    shards share one static layout signature under ``shard_map``.
    Forced padding only widens windows and adds zero-weight slots — the
    planned incidence/ownership content is unchanged.
    """
    from repro.core.partition import rcm_order_cached   # local: avoid cycle

    me = min_extents or {}
    V, E = graph.num_nodes, graph.num_edges
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    wts = np.asarray(graph.weights, np.float32)

    BV = int(block_nodes)
    nb = max(-(-max(V, 1) // BV), int(me.get("num_blocks", 1)))
    V_pad = nb * BV

    # 1. RCM relabel (bandwidth-minimizing => small halo windows); orders
    #    are memoized by structure hash, so re-planning an isomorphic
    #    graph (a serving session rebuilt after a data-only update) skips
    #    the BFS
    order = (rcm_order_cached(graph) if E
             else np.arange(V, dtype=np.int64))
    inv = np.empty(V, dtype=np.int64)
    inv[order] = np.arange(V)
    node_perm = np.full(V_pad, -1, dtype=np.int64)
    node_perm[:V] = order

    # 2. relabel + canonicalize edges in the new numbering; a flipped
    #    orientation (src > dst after relabel) negates the dual variable
    s2, d2 = inv[src], inv[dst]
    flip = s2 > d2
    lo = np.minimum(s2, d2)
    hi = np.maximum(s2, d2)
    eorder = np.lexsort((hi, lo))          # sorted rank -> original edge id
    lo, hi = lo[eorder], hi[eorder]
    w2, flip2 = wts[eorder], flip[eorder]

    # 3. owner block = block of the (smaller) src endpoint; lo is sorted,
    #    so each block's owned edges are already contiguous — pad to EB
    owner = lo // BV if E else np.zeros(0, np.int64)
    counts = np.bincount(owner, minlength=nb)
    EB = max(_round_up(int(counts.max()) if E else 1, 8),
             int(me.get("block_edges", 1)))
    E_pad = nb * EB
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    pos = (owner * EB + (np.arange(E) - starts[owner])) if E else \
        np.zeros(0, np.int64)

    src_l = np.zeros(E_pad, dtype=np.int64)
    dst_l = np.zeros(E_pad, dtype=np.int64)
    w_l = np.zeros(E_pad, dtype=np.float32)
    src_l[pos], dst_l[pos], w_l[pos] = lo, hi, w2
    edge_pos = np.empty(E, dtype=np.int64)
    edge_pos[eorder] = pos
    edge_flip = np.where(flip, -1.0, 1.0).astype(np.float32)

    # 4. per layout node, the first and last owned position of its
    #    incident edges: sort the (src, dst) endpoints by node and reduce
    #    each node's group
    deg_counts = np.zeros(V_pad, np.int64)
    node_emin = np.zeros(V_pad, np.int64)
    node_emax = np.zeros(V_pad, np.int64)
    if E:
        endpoints = np.concatenate([lo, hi])
        order2 = np.argsort(endpoints, kind="stable")
        epos = np.concatenate([pos, pos])[order2]
        deg_counts = np.bincount(endpoints, minlength=V_pad)
        group_start = np.concatenate([[0], np.cumsum(deg_counts)])[:-1]
        nz = deg_counts > 0
        node_emin[nz] = np.minimum.reduceat(epos, group_start[nz])
        node_emax[nz] = np.maximum.reduceat(epos, group_start[nz])

    # 5. halo extents.  Per block b the kernel needs (a) w rows for owned
    #    nodes and dst endpoints of owned edges, (b) u rows for every edge
    #    incident to those nodes.
    has_inc = deg_counts > 0
    kn = int(me.get("kn", 1))
    klo = int(me.get("klo", 0))
    khi = int(me.get("khi", 0))
    for b in range(nb):
        own = slice(b * EB, b * EB + int(counts[b]))
        needed = np.arange(b * BV, min((b + 1) * BV, V_pad))
        if counts[b]:
            needed = np.unique(np.concatenate([needed, dst_l[own]]))
        needed = needed[has_inc[needed]]
        if len(needed):
            kn = max(kn, -(-(int(needed.max()) + 1 - b * BV) // BV))
            emin = int(node_emin[needed].min())
            emax = int(node_emax[needed].max())
            klo = max(klo, -(-(b * EB - emin) // EB))
            khi = max(khi, -(-(emax + 1 - (b + 1) * EB) // EB))
    klo, khi = max(klo, 0), max(khi, 0)

    return EdgeBlockLayout(
        block_nodes=BV, num_blocks=nb, block_edges=EB, kn=int(kn),
        klo=int(klo), khi=int(khi), num_nodes=V, num_edges=E,
        node_perm=jnp.asarray(node_perm, jnp.int32),
        node_inv=jnp.asarray(inv, jnp.int32),
        src=jnp.asarray(src_l, jnp.int32),
        dst=jnp.asarray(dst_l, jnp.int32),
        weights=jnp.asarray(w_l),
        edge_pos=jnp.asarray(edge_pos, jnp.int32),
        edge_flip=jnp.asarray(edge_flip),
    )


# candidate banded block sizes for the auto-tuner; whole-graph single
# block is always considered as the fallback candidate
_BLOCK_LADDER = (256, 512, 1024, 2048)


def plan_edge_blocks(graph: EmpiricalGraph,
                     block_nodes: int | None = None, *,
                     window_hint: tuple | None = None,
                     min_extents: dict | None = None) -> EdgeBlockLayout:
    """Host-side edge-blocked layout pass (see :class:`EdgeBlockLayout`).

    RCM node reordering + per-block contiguous edge ranges with halo
    padding; the result is static aux the fused primal-dual kernel keys
    its BlockSpec index maps on.

    With ``block_nodes=None`` the block size is auto-tuned from
    ``EdgeBlockLayout.window_bytes``: candidate banded layouts (256 /
    512 / 1024 / 2048 nodes per block) are planned and scored by total
    streamed window bytes per iteration (``num_blocks * window_bytes``),
    the quantity the fused kernel is bound by once halo redundancy
    dominates.  ``window_hint = (num_features, param_floats, itemsize,
    max_window_bytes)`` makes the score dtype/loss-aware and rejects
    candidates whose single-window footprint exceeds the VMEM cap; when
    absent, a nominal (1, 0, 4, None) hint scores by row counts.  When
    even the best banded candidate's halo extents exceed 3 blocks (RCM
    banding defeated), a single whole-graph block is used instead — no
    redundant halo work, and it unlocks the multi-iteration VMEM fusion.

    ``min_extents`` (explicit ``block_nodes`` only) forces padded-extent
    lower bounds — see :func:`_plan_edge_blocks_fixed`.
    """
    V = graph.num_nodes
    if block_nodes is not None:
        return _plan_edge_blocks_fixed(graph, int(block_nodes), min_extents)
    whole = _round_up(V, 8)
    if V <= 512:
        return _plan_edge_blocks_fixed(graph, whole, min_extents)

    nf, pf, isz, cap = window_hint if window_hint is not None \
        else (1, 0, 4, None)
    best = best_cost = None
    for bv in _BLOCK_LADDER:
        if bv >= whole:
            break
        lt = _plan_edge_blocks_fixed(graph, bv, min_extents)
        wb = lt.window_bytes(nf, param_floats=pf, itemsize=isz)
        if cap is not None and wb > cap:
            continue
        cost = lt.num_blocks * wb
        if best is None or cost < best_cost:
            best, best_cost = lt, cost
    # quality guard: nb*kn*BV > 3*V_pad  <=>  kn > 3 (and likewise for the
    # edge window) — the historical redundancy bound, now applied to the
    # best candidate instead of a hardcoded 256-node block
    if (best is None or best.kn > 3
            or (best.klo + 1 + best.khi) > 3):
        single = _plan_edge_blocks_fixed(graph, whole, min_extents)
        swb = single.window_bytes(nf, param_floats=pf, itemsize=isz)
        if best is None or cap is None or swb <= cap:
            return single
    return best


def sbm_graph(rng: np.random.Generator, cluster_sizes, p_in: float,
              p_out: float, weight: float = 1.0) -> tuple[EmpiricalGraph, np.ndarray]:
    """Stochastic block model empirical graph (paper §5).

    Returns (graph, cluster_assignment). Nodes within a cluster are connected
    with prob p_in, across clusters with prob p_out; all edge weights A_e are
    ``weight``.
    """
    sizes = list(cluster_sizes)
    num_nodes = int(sum(sizes))
    assign = np.concatenate([np.full(s, c) for c, s in enumerate(sizes)])
    iu, ju = np.triu_indices(num_nodes, k=1)
    same = assign[iu] == assign[ju]
    p = np.where(same, p_in, p_out)
    keep = rng.random(len(iu)) < p
    edges = np.stack([iu[keep], ju[keep]], axis=1)
    weights = np.full(edges.shape[0], weight, dtype=np.float32)
    g = build_graph(edges, weights, num_nodes)
    return g, assign


def sbm_graph_sparse(rng: np.random.Generator, cluster_sizes, p_in: float,
                     p_out: float, weight: float = 1.0
                     ) -> tuple[EmpiricalGraph, np.ndarray]:
    """O(E) stochastic block model sampler for million-node graphs.

    :func:`sbm_graph` materializes all V(V-1)/2 candidate pairs — fine up
    to ~50k nodes, hopeless at 10^6.  This variant samples, per cluster
    pair, the Binomial(#pairs, p) edge *count* and then that many
    endpoint pairs uniformly at random.  Self-pairs are dropped and
    duplicate pairs collapse in ``build_graph``'s dedupe, a relative
    undercount of O(p * avg_degree / cluster_size) — negligible at the
    sparse densities this sampler exists for.  Same return convention as
    :func:`sbm_graph`.
    """
    sizes = [int(s) for s in cluster_sizes]
    num_nodes = int(sum(sizes))
    assign = np.concatenate([np.full(s, c) for c, s in enumerate(sizes)])
    offs = np.concatenate([[0], np.cumsum(sizes)])
    chunks = []
    for a in range(len(sizes)):
        for b in range(a, len(sizes)):
            p = float(min(p_in if a == b else p_out, 1.0))
            pairs = (sizes[a] * (sizes[a] - 1)) // 2 if a == b \
                else sizes[a] * sizes[b]
            if p <= 0.0 or pairs == 0:
                continue
            k = int(rng.binomial(pairs, p))
            if not k:
                continue
            i = rng.integers(offs[a], offs[a + 1], size=k)
            j = rng.integers(offs[b], offs[b + 1], size=k)
            keep = i != j
            chunks.append(np.stack([i[keep], j[keep]], axis=1))
    edges = (np.concatenate(chunks, axis=0) if chunks
             else np.zeros((0, 2), np.int64))
    g = build_graph(edges, np.full(len(edges), weight, np.float32),
                    num_nodes)
    return g, assign


def chain_graph(rng: np.random.Generator, num_nodes: int,
                weight: float = 1.0) -> EmpiricalGraph:
    """Path graph 0-1-...-(V-1) — the fused-lasso / changepoint structure.

    Every generator in this module takes a ``numpy.random.Generator`` as
    its first argument, deterministic families included, so scenario code
    can treat the whole zoo uniformly (same seed -> identical graph).
    """
    del rng  # deterministic family; accepted for the uniform signature
    e = np.stack([np.arange(num_nodes - 1), np.arange(1, num_nodes)], axis=1)
    return build_graph(e, np.full(num_nodes - 1, weight, np.float32), num_nodes)


def grid_graph(rng: np.random.Generator, rows: int, cols: int,
               weight: float = 1.0) -> EmpiricalGraph:
    """2-D lattice with 4-neighbour connectivity (image-denoising TV)."""
    del rng  # deterministic family; accepted for the uniform signature
    idx = np.arange(rows * cols).reshape(rows, cols)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    edges = np.concatenate([right, down], axis=0)
    return build_graph(edges, np.full(len(edges), weight, np.float32),
                       rows * cols)


def watts_strogatz_graph(rng: np.random.Generator, num_nodes: int,
                         k: int = 4, p_rewire: float = 0.1,
                         weight: float = 1.0) -> EmpiricalGraph:
    """Watts-Strogatz small world: ring lattice (k/2 neighbours per side)
    with each lattice edge rewired to a random endpoint with prob p_rewire.

    Rewiring keeps the source endpoint, never creates self-loops, and lets
    ``build_graph`` drop the (rare) duplicate edges, matching the usual
    construction.
    """
    if k % 2 or k <= 0:
        raise ValueError(f"k must be a positive even integer, got {k}")
    src, dst = [], []
    for hop in range(1, k // 2 + 1):
        i = np.arange(num_nodes)
        j = (i + hop) % num_nodes
        src.append(i)
        dst.append(j)
    src = np.concatenate(src)
    dst = np.concatenate(dst)
    rewire = rng.random(len(src)) < p_rewire
    new_dst = rng.integers(0, num_nodes, size=len(src))
    # avoid self-loops on rewired edges (shift by one when they collide)
    new_dst = np.where(new_dst == src, (new_dst + 1) % num_nodes, new_dst)
    dst = np.where(rewire, new_dst, dst)
    edges = np.stack([src, dst], axis=1)
    return build_graph(edges, np.full(len(edges), weight, np.float32),
                       num_nodes)


def barabasi_albert_graph(rng: np.random.Generator, num_nodes: int,
                          m: int = 2,
                          weight: float = 1.0) -> EmpiricalGraph:
    """Barabasi-Albert preferential attachment: hub-dominated degrees.

    Starts from a complete seed graph on m+1 nodes; each arriving node
    attaches to m distinct existing nodes sampled proportionally to degree
    (sampling from the repeated-endpoints list, the standard construction).
    """
    if not 1 <= m < num_nodes:
        raise ValueError(f"need 1 <= m < num_nodes, got m={m}, V={num_nodes}")
    seed_n = m + 1
    edges = [(i, j) for i in range(seed_n) for j in range(i + 1, seed_n)]
    # flat list of edge endpoints: sampling uniformly from it is sampling
    # nodes proportionally to degree
    endpoints = [v for e in edges for v in e]
    for v in range(seed_n, num_nodes):
        targets: set[int] = set()
        while len(targets) < m:
            targets.add(int(endpoints[rng.integers(0, len(endpoints))]))
        for t in targets:
            edges.append((t, v))
            endpoints.extend((t, v))
    edges = np.asarray(edges, dtype=np.int64)
    return build_graph(edges, np.full(len(edges), weight, np.float32),
                       num_nodes)


@partial(jax.jit, static_argnames=())
def graph_signal_mse(w_hat: jnp.ndarray, w_true: jnp.ndarray,
                     mask: jnp.ndarray) -> jnp.ndarray:
    """Paper eq. (24): (1/|V|) sum_{i in mask} ||wbar_i - what_i||_2^2."""
    sq = jnp.sum((w_hat - w_true) ** 2, axis=1)
    return jnp.sum(jnp.where(mask, sq, 0.0)) / w_hat.shape[0]
