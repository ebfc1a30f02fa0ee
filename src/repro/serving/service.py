"""SolveService: streaming, multi-tenant, warm-started GTVMin serving.

The serving story the rest of the repo builds toward: a service that
holds many live :class:`~repro.api.problem.Problem` instances as
*sessions* and answers solve requests against them, reusing plans
(RCM orders, edge-blocked layouts, XLA executables) across tenants via
the :class:`~repro.serving.cache.PlanCache` and warm-starting every
re-solve from the session's cached primal/dual state.

Request surface (all host-side, synchronous):

  * ``create_session(tenant, problem)``   — admit a problem.
  * ``update_session(id, delta, patch)``  — apply per-node data deltas
    (:class:`DataDelta`) and/or edge add/drop patches
    (:class:`EdgePatch`); duals survive the edge relabeling through
    :func:`repro.core.partition.transfer_edge_duals`.
  * ``solve(id)``                         — warm-started solve; returns
    a :class:`SolveResponse` carrying the eq.-11 residual certificate.
  * ``solve_path(id, lams)``              — batched lambda sweep.
  * ``close(id)``                         — evict the session.

Every response reports residual / iterations / cache / timing
diagnostics, and per-tenant :class:`~repro.serving.ledger.ServiceLedger`
instances meter the request stream the way the federated
``CommLedger`` meters bits on the wire.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.backends import _graph_layout, _should_fuse
from repro.api.problem import Problem, SolverConfig
from repro.api.solver import Solver, solve_path as _solve_path
from repro.core.graph import build_graph
from repro.core.partition import transfer_edge_duals
from repro.engine import capped as _capped
from repro.serving.cache import Plan, PlanCache, PlanKey
from repro.serving.ledger import ServiceLedger

#: Service-wide solve defaults: tol-certified runs at the empirically
#: reachable 1e-3 residual (EXPERIMENTS.md: small-lambda regimes
#: plateau above 1e-4), over-relaxed, chunked every 25 iterations.
DEFAULT_CONFIG = SolverConfig(num_iters=6000, rho=1.9, metric_every=25,
                              tol=1e-3, record_residual=True,
                              backend="dense")


@dataclasses.dataclass(frozen=True)
class DataDelta:
    """Per-node data replacement: new measurements for ``nodes``.

    Each non-None field carries one leading row per entry of ``nodes``
    and *replaces* that node's rows of the corresponding
    :class:`~repro.core.losses.NodeData` array — x: (k, m_max, n),
    y: (k, m_max), sample_mask: (k, m_max), labeled_mask: (k,).
    """

    nodes: tuple[int, ...]
    x: np.ndarray | None = None
    y: np.ndarray | None = None
    sample_mask: np.ndarray | None = None
    labeled_mask: np.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class EdgePatch:
    """Edge add/drop patch against a session's empirical graph.

    ``add`` holds (i, j, weight) triples, ``drop`` holds (i, j) pairs
    (either orientation; the graph is undirected).  The node set is
    fixed — patches may only rewire existing nodes.
    """

    add: tuple[tuple[int, int, float], ...] = ()
    drop: tuple[tuple[int, int], ...] = ()


@dataclasses.dataclass
class Session:
    """One live problem: the tenant's graph + data + warm solver state."""

    session_id: str
    tenant: str
    problem: Problem
    config: SolverConfig
    w: jnp.ndarray | None = None
    u: jnp.ndarray | None = None
    cold_iterations: int | None = None
    solves: int = 0
    updates: int = 0


@dataclasses.dataclass(frozen=True)
class SolveResponse:
    """One answered solve request: estimate + certificate + diagnostics.

    ``residual`` is the last entry of the eq.-11 fixed-point residual
    trace (the optimality certificate the SLA is stated in);
    ``certificate`` carries the eq.-11 dual-infeasibility /
    stationarity diagnostics; ``meets_sla`` is residual <= tol.

    Timing is measured, never re-run: ``seconds`` is the request's wall
    clock (plan lookup, warm state, solve, certificate and the
    response's host fetches); ``compiles`` counts the JAX compiles
    (backend compiles and compilation-cache loads, :func:`repro.obs.compiles`)
    that finished inside it, ``compile_seconds`` their summed seconds,
    and ``solve_seconds = seconds - compile_seconds``.  A path sweep or
    a batched group is answered by one program: each of its responses
    carries an even share of its seconds and compile seconds, and its
    first response carries its compiles.  ``compiled`` is the plan
    cache's "new executable signature" flag, not a measurement.
    ``queue_wait`` counts *submissions* (not wall time) the request sat
    behind in the serving queue; ``batch_width`` is the number of
    sessions solved by the same batched executable.
    """

    session_id: str
    w: jnp.ndarray
    objective: float
    residual: float
    certificate: dict
    lam: float
    tol: float | None
    iterations: int
    warm: bool
    cache_hit: bool
    compiled: bool
    seconds: float
    meets_sla: bool
    solve_seconds: float = 0.0
    compile_seconds: float = 0.0
    queue_wait: int = 0
    batch_width: int = 1
    compiles: int = 0


class _Timing(NamedTuple):
    """Wall seconds, compiles and compile seconds of one request."""

    seconds: float
    compiles: int
    compile_seconds: float

    def share(self, n: int, first: bool) -> "_Timing":
        """One of the ``n`` responses a single program answered: an even
        share of the seconds, the compiles on the first response."""
        return _Timing(self.seconds / n, self.compiles if first else 0,
                       self.compile_seconds / n)


class _Clock:
    """The wall clock and the process's compile tally from a request's
    start; :meth:`read` gives what the request has taken so far."""

    __slots__ = ("t0", "c0", "s0")

    def __init__(self):
        self.c0, self.s0 = obs.compiles()
        self.t0 = time.perf_counter()

    def read(self) -> _Timing:
        t = time.perf_counter()
        c, s = obs.compiles()
        return _Timing(t - self.t0, c - self.c0, s - self.s0)


def _fetch(result) -> tuple[float, float, dict]:
    """A response's host facts: the last eq.-11 residual, the last
    objective and the certificate's scalars (the request's sync)."""
    residual = (float(result.residual[-1])
                if result.residual is not None else float("nan"))
    certificate = {k: float(v)
                   for k, v in result.diagnostics.items()
                   if k not in ("iterations", "route")
                   and not k.startswith("halo_") and np.ndim(v) == 0}
    return residual, float(result.objective[-1]), certificate


class SolveService:
    """Multi-tenant warm-started solve service over a shared plan cache."""

    def __init__(self, config: SolverConfig | None = None,
                 max_plans: int = 64):
        cfg = config if config is not None else DEFAULT_CONFIG
        if cfg.backend not in ("dense", "pallas"):
            raise ValueError(
                "SolveService serves the single-program engines; backend "
                f"must be 'dense' or 'pallas', got {cfg.backend!r}")
        self.config = cfg
        self.plans = PlanCache(max_entries=max_plans)
        self._sessions: dict[str, Session] = {}
        self._ledgers: dict[str, ServiceLedger] = {}

    # -- bookkeeping ---------------------------------------------------------
    def ledger(self, tenant: str) -> ServiceLedger:
        led = self._ledgers.get(tenant)
        if led is None:
            led = self._ledgers[tenant] = ServiceLedger(tenant=tenant)
        return led

    def summary(self) -> dict:
        """Service-wide report: per-tenant ledgers + plan-cache stats."""
        return {
            "tenants": {t: led.summary()
                        for t, led in sorted(self._ledgers.items())},
            "plan_cache": self.plans.summary(),
            "sessions": float(len(self._sessions)),
        }

    def session(self, session_id: str) -> Session:
        try:
            return self._sessions[session_id]
        except KeyError:
            raise KeyError(f"unknown session {session_id!r}") from None

    # -- plan persistence ----------------------------------------------------
    def save_plans(self, path: str) -> dict[str, int]:
        """Persist the plan cache (layouts + RCM orders) to ``path``.

        A restarted service calls :meth:`load_plans` and skips
        re-planning for every structure saved here (it still pays the
        XLA traces — executables die with the process).
        """
        return self.plans.save(path)

    def load_plans(self, path: str) -> dict[str, int]:
        """Restore plans saved by :meth:`save_plans` (hash-validated)."""
        return self.plans.load(path)

    # -- session lifecycle ---------------------------------------------------
    def create_session(self, tenant: str, problem: Problem,
                       config: SolverConfig | None = None) -> str:
        """Admit ``problem`` for ``tenant``; returns the session id.

        Sessions are keyed by tenant + graph structure hash (with a
        ``#k`` suffix when a tenant serves the same structure twice).
        """
        cfg = config if config is not None else self.config
        base = f"{tenant}/{problem.graph.structure_hash()[:12]}"
        session_id, k = base, 1
        while session_id in self._sessions:
            session_id = f"{base}#{k}"
            k += 1
        self._sessions[session_id] = Session(
            session_id=session_id, tenant=tenant, problem=problem,
            config=cfg)
        led = self.ledger(tenant)
        led.requests += 1
        led.creates += 1
        return session_id

    def update_session(self, session_id: str,
                       delta: DataDelta | None = None,
                       patch: EdgePatch | None = None,
                       lam: float | None = None) -> None:
        """Apply data deltas / edge patches; warm state survives.

        Data deltas replace node rows in place; edge patches rebuild the
        graph (new structure hash — the next solve re-plans) and carry
        the cached duals across the edge relabeling, zero-filling the
        rows of added edges.  ``lam`` retargets the TV strength.
        """
        sess = self.session(session_id)
        with obs.span("service_update_session", session=session_id):
            if delta is not None:
                sess.problem = dataclasses.replace(
                    sess.problem, data=_apply_delta(sess.problem.data, delta))
            if patch is not None:
                old_graph = sess.problem.graph
                new_graph = _apply_patch(old_graph, patch)
                if sess.u is not None:
                    sess.u = jnp.asarray(transfer_edge_duals(
                        old_graph, new_graph, np.asarray(sess.u)))
                sess.problem = dataclasses.replace(sess.problem,
                                                   graph=new_graph)
            if lam is not None:
                sess.problem = sess.problem.with_lam(float(lam))
            if patch is not None or lam is not None:
                # the cold baseline measured a *different* problem (other
                # structure / other lambda); the next cold-reference solve
                # re-establishes it, so warm_iteration_ratio never mixes
                sess.cold_iterations = None
            sess.updates += 1
            led = self.ledger(sess.tenant)
            led.requests += 1
            led.updates += 1

    def close(self, session_id: str) -> None:
        sess = self._sessions.pop(session_id, None)
        if sess is None:
            raise KeyError(f"unknown session {session_id!r}")
        led = self.ledger(sess.tenant)
        led.requests += 1
        led.closes += 1

    # -- solving -------------------------------------------------------------
    def _plan(self, problem: Problem, config: SolverConfig,
              sig: tuple | None = None) -> tuple[Plan, bool, bool]:
        with obs.span("service_plan"):
            key = PlanKey.for_problem(problem, config)

            def build() -> Plan:
                layout = None
                if (config.backend == "pallas"
                        and _should_fuse(problem, config)
                        and problem.graph.num_edges):
                    # the layout the fusion gate sized against the VMEM cap
                    layout = _graph_layout(problem.graph)
                return Plan(key=key, layout=layout)

            return self.plans.get_or_build(key, build, sig=sig)

    def _with_plan(self, problem: Problem, plan: Plan) -> Problem:
        if plan.layout is None or problem.graph.layout is plan.layout:
            return problem
        return dataclasses.replace(
            problem,
            graph=dataclasses.replace(problem.graph, layout=plan.layout))

    def solve(self, session_id: str, *, w_true=None, cold: bool = False,
              queue_wait: int = 0) -> SolveResponse:
        """Solve the session's problem, warm-starting from cached state.

        ``cold=True`` forces a from-zeros solve (benchmark baseline);
        warm starts re-project the cached duals onto the current
        lambda's feasible box, so a lambda retarget stays feasible.
        ``queue_wait`` is forwarded verbatim into the response and the
        request event (the serving queue passes each ticket's measured
        wait; direct callers leave it 0).
        """
        sess = self.session(session_id)
        with obs.span("service_solve", session=session_id,
                      request=sess.solves + 1):
            clock = _Clock()
            cfg = sess.config
            plan, hit, compiled = self._plan(sess.problem, cfg)
            problem = self._with_plan(sess.problem, plan)

            warm = sess.w is not None and not cold
            w0, u0 = (self._warm_state(sess.w, sess.u, problem) if warm
                      else (None, None))
            result = Solver(cfg).run(problem, w0=w0, u0=u0, w_true=w_true)

            iterations = int(result.diagnostics.get(
                "iterations", _capped(cfg.num_iters, cfg.metric_every)))
            sess.w, sess.u = result.w, result.u
            sess.solves += 1
            cold_ref = sess.cold_iterations if warm else None
            if not warm:
                # only true from-zeros solves (first solve, forced cold,
                # post-update-reset) may define the cold baseline — a warm
                # solve standing in as baseline would fake the ratio
                sess.cold_iterations = iterations

            led = self.ledger(sess.tenant)
            led.requests += 1
            led.record_solve(cache_hit=hit, compiled=compiled,
                             iterations=iterations, cold_ref=cold_ref)
            with obs.span("service_response"):
                facts = _fetch(result)
                jax.block_until_ready(result.w)
                return self._response(sess, result, facts, clock.read(),
                                      warm=warm, cache_hit=hit,
                                      compiled=compiled, iterations=iterations,
                                      queue_wait=queue_wait)

    @staticmethod
    def _warm_state(w, u, problem: Problem):
        """Warm-start copies of a session's cached state (backends donate
        warm-start buffers on TPU/GPU), the duals re-projected onto the
        current lambda's feasible box."""
        with obs.span("service_warm_state"):
            w0 = jnp.copy(w)
            u0 = problem.regularizer.project_dual(
                jnp.copy(u), problem.graph, problem.lam)
            return w0, u0

    def solve_path(self, session_id: str, lams,
                   *, w_true=None) -> list[SolveResponse]:
        """Batched lambda sweep against the session (vmapped engine).

        Path solves are read-only — they answer "what would the estimate
        be at these lambdas" without disturbing the session's warm state
        or its current lambda.
        """
        clock = _Clock()
        sess = self.session(session_id)
        lams = np.asarray(lams, np.float32).reshape(-1)
        # fixed-length vmapped scan: tol off, residual trace on
        cfg = sess.config.replace(tol=None, record_residual=True,
                                  continuation=False)
        plan, hit, compiled = self._plan(sess.problem, cfg)
        problem = self._with_plan(sess.problem, plan)
        result = _solve_path(problem, lams, cfg, w_true=w_true)

        iters = _capped(cfg.final_iters, cfg.metric_every)
        warm_iters = _capped(cfg.warm_iters, cfg.metric_every)
        led = self.ledger(sess.tenant)
        led.requests += 1
        led.record_path(points=len(lams), point_iterations=iters,
                        warm_iterations=warm_iters, cache_hit=hit,
                        compiled=compiled)
        points = [jax.tree_util.tree_map(lambda a, i=i: a[i], result)
                  for i in range(len(lams))]
        facts = [_fetch(point) for point in points]
        jax.block_until_ready(result.w)
        timing = clock.read()
        npts = max(len(lams), 1)
        return [self._response(
                    sess, point, fact, timing.share(npts, i == 0),
                    warm=False, cache_hit=hit,
                    compiled=compiled if i == 0 else False,
                    iterations=iters, tol=sess.config.tol, kind="path")
                for i, (point, fact) in enumerate(zip(points, facts))]

    def _response(self, sess: Session, result, facts: tuple,
                  timing: _Timing, *, warm: bool, cache_hit: bool,
                  compiled: bool, iterations: int,
                  tol: float | None = ..., queue_wait: int = 0,
                  batch_width: int = 1,
                  kind: str = "solve") -> SolveResponse:
        tol = sess.config.tol if tol is ... else tol
        residual, objective, certificate = facts
        resp = SolveResponse(
            session_id=sess.session_id,
            w=result.w,
            objective=objective,
            residual=residual,
            certificate=certificate,
            lam=float(result.lam),
            tol=tol,
            iterations=iterations,
            warm=warm,
            cache_hit=cache_hit,
            compiled=compiled,
            seconds=timing.seconds,
            meets_sla=bool(tol is not None and residual <= tol),
            solve_seconds=timing.seconds - timing.compile_seconds,
            compile_seconds=timing.compile_seconds,
            queue_wait=queue_wait,
            batch_width=batch_width,
            compiles=timing.compiles,
        )
        if obs.enabled():
            self._record_obs(sess, resp, kind=kind)
        return resp

    def _record_obs(self, sess: Session, resp: SolveResponse, *,
                    kind: str) -> None:
        """Meter one response into the obs registry + event log."""
        obs.counter("repro_serving_requests_total",
                    help="solve responses by tenant and kind",
                    tenant=sess.tenant, kind=kind).inc()
        obs.histogram("repro_serving_request_seconds",
                      help="request wall clock (compile included)"
                      ).observe(resp.seconds)
        obs.histogram("repro_serving_execute_seconds",
                      help="request seconds outside JAX compiles"
                      ).observe(resp.solve_seconds)
        obs.histogram("repro_serving_queue_wait",
                      help="submissions a request waited behind",
                      buckets=obs.COUNT_BUCKETS
                      ).observe(float(resp.queue_wait))
        obs.histogram("repro_serving_batch_width",
                      help="sessions per batched executable",
                      buckets=obs.COUNT_BUCKETS
                      ).observe(float(resp.batch_width))
        obs.counter("repro_serving_sla_total",
                    help="responses by SLA outcome",
                    outcome="met" if resp.meets_sla else "missed").inc()
        obs.counter("repro_serving_iterations_total",
                    help="solver iterations run by the service"
                    ).inc(float(resp.iterations))
        self.ledger(sess.tenant).export_obs()
        obs.events.record_request(
            event=kind, tenant=sess.tenant, session=sess.session_id,
            queue_wait=resp.queue_wait, batch_width=resp.batch_width,
            warm=resp.warm, cache_hit=resp.cache_hit,
            compiled=resp.compiled, iterations=resp.iterations,
            residual=resp.residual, meets_sla=resp.meets_sla,
            seconds=resp.seconds, solve_seconds=resp.solve_seconds,
            compile_seconds=resp.compile_seconds, lam=resp.lam,
            tol=resp.tol)


# ---------------------------------------------------------------------------
# Patch application helpers (host-side)
# ---------------------------------------------------------------------------

def _apply_delta(data, delta: DataDelta):
    """Row-replace ``delta.nodes`` in each provided NodeData field."""
    nodes = jnp.asarray(delta.nodes, jnp.int32)
    out = data
    for field in ("x", "y", "sample_mask", "labeled_mask"):
        rows = getattr(delta, field)
        if rows is None:
            continue
        cur = getattr(out, field)
        rows = jnp.asarray(rows, cur.dtype)
        if rows.shape != (len(delta.nodes),) + cur.shape[1:]:
            raise ValueError(
                f"DataDelta.{field} must have shape "
                f"{(len(delta.nodes),) + cur.shape[1:]}, got {rows.shape}")
        out = dataclasses.replace(out, **{field: cur.at[nodes].set(rows)})
    return out


def _apply_patch(graph, patch: EdgePatch):
    """Rebuild the graph with ``patch`` applied (canonicalized edges).

    Drops first, then adds in patch order with *last-write-wins*
    semantics: adding an edge that already exists (or was dropped and
    re-added within the same patch) re-weights it.  ``build_graph``'s
    stable dedupe keeps the first duplicate, so appending and rebuilding
    would silently keep the stale weight instead.  Self-loop adds are
    rejected here, naming the offending pair, rather than surfacing as
    a late anonymous build_graph error.
    """
    V = graph.num_nodes
    edges: "dict[tuple[int, int], float]" = {
        (int(s), int(d)): float(w)
        for s, d, w in zip(np.asarray(graph.src, np.int64),
                           np.asarray(graph.dst, np.int64),
                           np.asarray(graph.weights, np.float32))}
    for i, j in patch.drop:
        edges.pop((min(i, j), max(i, j)), None)
    for i, j, w in patch.add:
        if i == j:
            raise ValueError(
                f"EdgePatch.add contains the self-loop ({i}, {j}); the "
                "empirical graph couples distinct local datasets")
        if not (0 <= i < V and 0 <= j < V):
            raise ValueError(f"edge ({i}, {j}) outside the node set "
                             f"[0, {V})")
        edges[(min(i, j), max(i, j))] = float(w)
    if edges:
        items = sorted(edges.items())
        pairs = np.asarray([k for k, _ in items], np.int64)
        wts = np.asarray([w for _, w in items], np.float32)
    else:
        pairs = np.zeros((0, 2), np.int64)
        wts = np.zeros((0,), np.float32)
    return build_graph(pairs, wts, V)
