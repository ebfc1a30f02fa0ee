"""Plan/executable cache for the solve service.

A *plan* is everything a solve reuses that is expensive to rebuild but
independent of the node-local data: the RCM order (memoized in
``core.partition``), the :class:`~repro.core.graph.EdgeBlockLayout` for
the fused pallas engine, and — through XLA's own executable cache — the
compiled solve chunks.  Plans are keyed by

    (graph structure hash, loss, regularizer, backend, shape signature)

so two tenants serving the same graph *structure* with different data
share one plan, while any edge add/drop/reweight (new structure hash)
builds a fresh one.

Compile accounting rides the *executable signature* — the plan key minus
the structure hash.  XLA caches jitted executables by static args and
shapes, not by graph content, so a plan-cache miss only pays an XLA
trace when its exec-sig is new too; ``PlanCache`` tracks both so the
:class:`~repro.serving.ledger.ServiceLedger` can report honest compile
counts.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from collections import OrderedDict
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.problem import Problem, SolverConfig
from repro.core.graph import EdgeBlockLayout


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Cache key: structure + templates + backend + shapes.

    ``loss`` / ``regularizer`` are the template reprs (dataclass reprs
    are stable and capture parameters like a lasso alpha); ``shape_sig``
    is (V, E, m_max, n, max_degree) — the tuple that determines every
    traced array shape of the solve.  ``shard_sig`` is the sharding
    facet for the distributed backends — (num_shards, mesh_axis,
    partitioner, comm) — empty for single-program backends, so two
    sessions solving the same structure under different meshes or
    exchange modes never share a plan or an executable.
    """

    structure_hash: str
    loss: str
    regularizer: str
    backend: str
    shape_sig: tuple[int, int, int, int, int]
    shard_sig: tuple = ()

    @classmethod
    def for_problem(cls, problem: Problem,
                    config: SolverConfig) -> "PlanKey":
        g, d = problem.graph, problem.data
        shard_sig: tuple = ()
        if config.backend in ("sharded", "sharded_fused"):
            mesh = config.mesh
            num_shards = (config.num_shards if config.num_shards is not None
                          else (mesh.shape[config.mesh_axis]
                                if mesh is not None else 1))
            shard_sig = (int(num_shards), str(config.mesh_axis),
                         str(config.partitioner), str(config.comm))
        return cls(
            structure_hash=g.structure_hash(),
            loss=repr(problem.loss),
            regularizer=repr(problem.regularizer),
            backend=config.backend,
            shape_sig=(g.num_nodes, g.num_edges, int(d.x.shape[1]),
                       int(d.x.shape[2]), g.max_degree),
            shard_sig=shard_sig,
        )

    @property
    def exec_sig(self) -> tuple:
        """The XLA-executable facet of the key (no structure hash)."""
        return (self.loss, self.regularizer, self.backend, self.shape_sig,
                self.shard_sig)


@dataclasses.dataclass
class Plan:
    """One cached solve plan.

    ``layout`` is the pre-planned edge-blocked layout (pallas backend;
    None for dense, whose only plan state is the memoized RCM order and
    the XLA executable).  ``uses`` counts lookups that returned this
    plan, hit or miss.
    """

    key: PlanKey
    layout: EdgeBlockLayout | None = None
    uses: int = 0


# EdgeBlockLayout field split for (de)serialization: python ints vs the
# device arrays that go through repro.checkpoint.
_LAYOUT_STATIC = ("block_nodes", "num_blocks", "block_edges", "kn", "klo",
                  "khi", "num_nodes", "num_edges")
_LAYOUT_ARRAYS = ("node_perm", "node_inv", "src", "dst", "weights",
                  "edge_pos", "edge_flip")
# plans.json format; 2 dropped the layouts' per-node incidence tables
_PLANS_VERSION = 2


def _payload_hash(arrays: "OrderedDict[str, np.ndarray]") -> str:
    """Content hash of a named array bundle (shape/dtype/bytes)."""
    h = hashlib.blake2b(digest_size=16)
    for name, arr in arrays.items():
        arr = np.ascontiguousarray(arr)
        h.update(name.encode())
        h.update(str(arr.dtype).encode())
        h.update(np.asarray(arr.shape, np.int64).tobytes())
        h.update(arr.tobytes())
    return h.hexdigest()


def layout_structure_hash(layout: EdgeBlockLayout) -> str:
    """Recompute the *original* graph's structure hash from a layout.

    Inverts the edge-block relabeling: original edge e lives at owned
    position ``edge_pos[e]`` with endpoints in layout numbering, so
    mapping through ``node_perm`` and re-canonicalizing (min/max — the
    original graph stores src < dst) reproduces exactly the arrays
    :meth:`EmpiricalGraph.structure_hash` hashes.  Used to validate a
    deserialized plan against the structure hash it claims to serve.
    """
    node_perm = np.asarray(layout.node_perm, np.int64)
    pos = np.asarray(layout.edge_pos, np.int64)
    a = node_perm[np.asarray(layout.src, np.int64)[pos]]
    b = node_perm[np.asarray(layout.dst, np.int64)[pos]]
    h = hashlib.blake2b(digest_size=16)
    h.update(np.int64(layout.num_nodes).tobytes())
    h.update(np.minimum(a, b).tobytes())
    h.update(np.maximum(a, b).tobytes())
    h.update(np.asarray(layout.weights, np.float32)[pos].tobytes())
    return h.hexdigest()


class PlanCache:
    """LRU cache of :class:`Plan` objects, capped at ``max_entries``.

    ``get_or_build`` is the main entry point: it returns ``(plan, hit,
    compiled)`` where ``hit`` is a plan-cache hit and ``compiled`` marks
    a lookup whose executable signature is new to this *process* (the
    solve will pay an XLA trace).  A hit can still report
    ``compiled=True`` for a plan restored by :meth:`load` — plans
    persist across processes, XLA executables do not.

    :meth:`save`/:meth:`load` persist the plans (layouts + the RCM
    orders they were planned from) through ``repro.checkpoint``, keyed
    and validated by structure hash.
    """

    def __init__(self, max_entries: int = 64):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = int(max_entries)
        self._plans: OrderedDict[PlanKey, Plan] = OrderedDict()
        # exec sigs this process has traced.  Bounded LRU: evicting a
        # *plan* never forgets its executable (XLA's own cache keeps it),
        # so the bound is a generous multiple of the plan cap rather
        # than tied to it.
        self._compiled_sigs: OrderedDict[tuple, None] = OrderedDict()
        self.compiled_sigs_max = max(8 * self.max_entries, 64)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.loaded = 0

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._plans

    def mark_compiled(self, sig: tuple) -> bool:
        """Record an executable signature; True iff new to this process.

        Public so the batch runner can meter its own vmapped
        executables (their sig includes the batch width).
        """
        if sig in self._compiled_sigs:
            self._compiled_sigs.move_to_end(sig)
            return False
        self._compiled_sigs[sig] = None
        while len(self._compiled_sigs) > self.compiled_sigs_max:
            self._compiled_sigs.popitem(last=False)
        if obs.enabled():
            obs.counter("repro_plan_compiles_total",
                        help="executable signatures newly traced").inc()
        return True

    def get_or_build(self, key: PlanKey, build: Callable[[], Plan],
                     *, sig: tuple | None = None) -> tuple[Plan, bool, bool]:
        """Look up (or build) the plan for ``key``.

        ``sig`` overrides the executable signature being metered — the
        batch runner passes ``("batch", B) + key.exec_sig`` because a
        vmapped executable is a different XLA trace than the singleton
        one, even over the same plan.
        """
        sig = key.exec_sig if sig is None else sig
        plan = self._plans.get(key)
        if plan is not None:
            self._plans.move_to_end(key)
            self.hits += 1
            plan.uses += 1
            if obs.enabled():
                self._export_obs(hit=True)
            # restored plans (cross-process load) hit here without this
            # process ever having traced the executable — still a compile
            return plan, True, self.mark_compiled(sig)
        self.misses += 1
        plan = build()
        # the sig is recorded only now: a failing build must not mark
        # its executable compiled, or the retry under-reports the trace
        compiled = self.mark_compiled(sig)
        plan.uses += 1
        self._plans[key] = plan
        while len(self._plans) > self.max_entries:
            self._plans.popitem(last=False)
            self.evictions += 1
        if obs.enabled():
            self._export_obs(hit=False)
        return plan, False, compiled

    def _export_obs(self, *, hit: bool) -> None:
        outcome = "hit" if hit else "miss"
        obs.counter("repro_plan_cache_lookups_total",
                    help="plan-cache lookups by outcome",
                    outcome=outcome).inc()
        obs.gauge("repro_plan_cache_entries",
                  help="plans currently cached").set(len(self._plans))

    # -- cross-process persistence ------------------------------------------
    def save(self, path: str) -> dict[str, int]:
        """Persist every cached plan (and its RCM order) to ``path``.

        Arrays go through ``repro.checkpoint`` (npz + manifest); a
        ``plans.json`` sidecar records keys, layout statics, array specs
        and content hashes so :meth:`load` can rebuild and validate the
        exact pytrees.  Compiled-sig state is deliberately *not* saved:
        XLA executables die with the process, and pretending otherwise
        would fake the compile accounting.
        """
        from repro.checkpoint import checkpoint as ckpt
        from repro.core.partition import export_rcm_orders

        trees: dict[str, dict[str, np.ndarray]] = {}
        plan_metas = []
        for idx, plan in enumerate(self._plans.values()):
            name = f"plan{idx}"
            entry: dict = {
                "name": name,
                "key": {
                    "structure_hash": plan.key.structure_hash,
                    "loss": plan.key.loss,
                    "regularizer": plan.key.regularizer,
                    "backend": plan.key.backend,
                    "shape_sig": list(plan.key.shape_sig),
                    "shard_sig": list(plan.key.shard_sig),
                },
                "layout": None,
            }
            if plan.layout is not None:
                arrays = OrderedDict(
                    (f, np.asarray(getattr(plan.layout, f)))
                    for f in _LAYOUT_ARRAYS)
                trees[name] = dict(arrays)
                entry["layout"] = {
                    "static": {f: int(getattr(plan.layout, f))
                               for f in _LAYOUT_STATIC},
                    "arrays": {f: {"shape": list(a.shape),
                                   "dtype": str(a.dtype)}
                               for f, a in arrays.items()},
                    "payload_hash": _payload_hash(arrays),
                }
            plan_metas.append(entry)

        # RCM orders for the structures we cache plans for (int32 storage:
        # checkpoint restore round-trips through jnp, which has no x64)
        hashes = {p.key.structure_hash for p in self._plans.values()}
        rcm_metas = []
        for idx, ((shash, reverse), order) in enumerate(
                sorted(export_rcm_orders(hashes).items())):
            name = f"rcm{idx}"
            arrays = OrderedDict(order=np.asarray(order, np.int32))
            trees[name] = dict(arrays)
            rcm_metas.append({
                "name": name, "structure_hash": shash,
                "reverse": bool(reverse), "shape": [int(len(order))],
                "payload_hash": _payload_hash(arrays),
            })

        ckpt.save(path, trees)
        with open(os.path.join(path, "plans.json"), "w") as f:
            json.dump({"version": _PLANS_VERSION, "plans": plan_metas,
                       "rcm_orders": rcm_metas}, f, indent=1, sort_keys=True)
        return {"plans": len(plan_metas), "rcm_orders": len(rcm_metas)}

    def load(self, path: str) -> dict[str, int]:
        """Restore plans saved by :meth:`save` into this cache.

        Every layout payload is content-hash checked, and every
        layout-bearing plan is re-validated against its claimed
        structure hash by *recomputing* the hash from the deserialized
        layout (:func:`layout_structure_hash`) — a stale or corrupted
        checkpoint raises instead of silently serving a wrong plan.
        RCM orders are reinstalled into the ``core.partition`` memo so
        any re-planning also skips the BFS.
        """
        from repro.checkpoint import checkpoint as ckpt
        from repro.core.partition import install_rcm_order

        with open(os.path.join(path, "plans.json")) as f:
            meta = json.load(f)
        if meta.get("version") != _PLANS_VERSION:
            raise ValueError(
                f"plan checkpoint {path} has format version "
                f"{meta.get('version')}; this build reads {_PLANS_VERSION}")

        like: dict[str, dict[str, np.ndarray]] = {}
        for entry in meta["plans"]:
            if entry["layout"] is not None:
                like[entry["name"]] = {
                    f: np.zeros(spec["shape"], dtype=spec["dtype"])
                    for f, spec in entry["layout"]["arrays"].items()}
        for entry in meta["rcm_orders"]:
            like[entry["name"]] = {
                "order": np.zeros(entry["shape"], np.int32)}
        restored = ckpt.restore(path, like) if like else {}

        loaded = 0
        for entry in meta["plans"]:
            k = entry["key"]
            key = PlanKey(structure_hash=k["structure_hash"],
                          loss=k["loss"], regularizer=k["regularizer"],
                          backend=k["backend"],
                          shape_sig=tuple(int(s) for s in k["shape_sig"]),
                          # pre-shard_sig checkpoints load as single-
                          # program plans (the field's default)
                          shard_sig=tuple(k.get("shard_sig", [])))
            layout = None
            if entry["layout"] is not None:
                arrays = OrderedDict(
                    (f, np.asarray(restored[entry["name"]][f]))
                    for f in _LAYOUT_ARRAYS)
                if _payload_hash(arrays) != entry["layout"]["payload_hash"]:
                    raise ValueError(
                        f"plan checkpoint corrupt: payload hash mismatch "
                        f"for {entry['name']} in {path}")
                layout = EdgeBlockLayout(
                    **{f: int(v)
                       for f, v in entry["layout"]["static"].items()},
                    **{f: jnp.asarray(v) for f, v in arrays.items()})
                recomputed = layout_structure_hash(layout)
                if recomputed != key.structure_hash:
                    raise ValueError(
                        f"plan checkpoint stale: {entry['name']} claims "
                        f"structure {key.structure_hash} but its layout "
                        f"hashes to {recomputed}")
            self._plans[key] = Plan(key=key, layout=layout)
            self._plans.move_to_end(key)
            loaded += 1
            while len(self._plans) > self.max_entries:
                self._plans.popitem(last=False)
                self.evictions += 1

        for entry in meta["rcm_orders"]:
            arrays = OrderedDict(
                order=np.asarray(restored[entry["name"]]["order"]))
            if _payload_hash(arrays) != entry["payload_hash"]:
                raise ValueError(
                    f"plan checkpoint corrupt: payload hash mismatch for "
                    f"{entry['name']} in {path}")
            install_rcm_order(entry["structure_hash"], arrays["order"],
                              reverse=entry["reverse"])

        self.loaded += loaded
        return {"plans": loaded, "rcm_orders": len(meta["rcm_orders"])}

    def summary(self) -> dict[str, float]:
        total = self.hits + self.misses
        return {
            "entries": float(len(self._plans)),
            "hits": float(self.hits),
            "misses": float(self.misses),
            "hit_rate": float(self.hits / total) if total else 0.0,
            "evictions": float(self.evictions),
            "compiled_sigs": float(len(self._compiled_sigs)),
            "loaded": float(self.loaded),
        }
