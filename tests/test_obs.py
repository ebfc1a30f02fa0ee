"""repro.obs: zero overhead while off, honest telemetry while on.

The contracts this suite pins:

  * **off is free** — with telemetry disabled (the default) and no
    profile being recorded, spans are the profiler's own no-op
    annotation (no clock read, no registry), metric mutations do
    nothing, and the solver's transfer discipline is unchanged: a tol
    solve still performs exactly one device->host fetch (PR 8's
    transfer-guard test, now run against the *library-level* counter
    in ``obs.device_fetch``),
  * **on is exact** — counters/histograms/events record what actually
    happened: one transfer counted per tol solve, plan-cache compile
    counts, JAX's compiles as JAX reports them, one schema-valid JSONL
    event per serving response, a response's measured compile/execute
    split with no solve run twice, and finite ledger gauges even for
    empty ledgers,
  * **spans reach the profile** — a CPU profile of a solve or a served
    request holds the ``repro.*`` spans, nested as the code nests them,
    with the request's identifiers as stats.
"""
import glob
import json

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.api import Solver, SolverConfig
from repro.federated.ledger import CommLedger
from repro.obs import events as obs_events
from repro.obs import export as obs_export
from repro.obs import telemetry
from repro.scenarios import get_scenario
from repro.serving import ServingQueue, SolveService
from repro.serving.cache import PlanCache, PlanKey
from repro.serving.ledger import ServiceLedger

from test_device_stop import TOL_CONF, _count_device_gets


@pytest.fixture
def fresh_obs():
    """Telemetry off, registry and event log empty; restored after."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture
def obs_on(fresh_obs):
    obs.enable()
    yield
    obs.disable()


def _scenario_problem():
    return get_scenario("sbm_regression").build(seed=0, smoke=True,
                                                lam=1e-2).problem


def _serve_cfg():
    return SolverConfig(num_iters=2000, rho=1.9, metric_every=25,
                        tol=1e-3, record_residual=True)


# ---------------------------------------------------------------------------
# telemetry primitives
# ---------------------------------------------------------------------------

def test_disabled_is_noop(fresh_obs, monkeypatch):
    c = obs.counter("t_total")
    g = obs.gauge("t_gauge")
    h = obs.histogram("t_seconds")
    c.inc()
    g.set(5.0)
    h.observe(0.1)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0
    # with no profile recording, a span is the profiler's own no-op
    # annotation: no clock read, no registry lookup, nothing kept
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert isinstance(obs.span("anything"), jax.profiler.TraceAnnotation)

    def no_clock():
        raise AssertionError("a disabled span read the clock")

    monkeypatch.setattr(telemetry.time, "perf_counter", no_clock)
    monkeypatch.setattr(obs.REGISTRY, "get", no_clock)
    with obs.span("anything", session="s", request=1):
        pass
    monkeypatch.undo()
    assert obs.REGISTRY.find("repro_span_seconds") == []
    assert obs.profiled_spans() == []


def test_metrics_record_when_enabled(obs_on):
    c = obs.counter("t_total", help="h")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    obs.gauge("t_gauge").set(-1.0)
    assert obs.gauge("t_gauge").value == -1.0
    h = obs.histogram("t_seconds")
    for v in (0.001, 0.002, 0.004, 0.3):
        h.observe(v)
    assert h.count == 4
    assert 0.001 <= h.percentile(0.5) <= 0.004
    assert h.percentile(0.99) <= 30.0
    # labeled metrics are distinct series under one name
    obs.counter("t_lab", tenant="a").inc()
    obs.counter("t_lab", tenant="b").inc(2)
    assert {m.value for m in obs.REGISTRY.find("t_lab")} == {1.0, 2.0}


def test_span_records_duration(obs_on):
    with obs.span("phase_x", session="s"):
        pass
    (h,) = obs.REGISTRY.find("repro_span_seconds")
    # the span's stats go to the profile, not into the histogram's labels
    assert h.count == 1 and dict(h.labels) == {"span": "phase_x"}
    assert obs.profiled_spans() == []      # no profile was recording


def test_compiles_counts_jax_compiles(fresh_obs):
    """The tally counts every backend compile, enabled or not, and
    nothing for a call whose program is already compiled."""
    f = jax.jit(lambda x: x * 3.25 + 1.0)
    x = jnp.arange(13.0)
    x.block_until_ready()
    n0, s0 = obs.compiles()
    f(x).block_until_ready()
    n1, s1 = obs.compiles()
    assert n1 - n0 == 1 and s1 > s0
    f(x).block_until_ready()
    assert obs.compiles() == (n1, s1)
    assert obs.REGISTRY.find("repro_compiles_total") == []


def test_compile_counters_when_enabled(obs_on):
    n0, s0 = obs.compiles()
    jax.jit(lambda x: x - 7.5)(jnp.arange(17.0)).block_until_ready()
    n1, s1 = obs.compiles()
    assert n1 > n0
    assert obs.counter("repro_compiles_total").value == n1 - n0
    assert obs.counter("repro_compile_seconds_total").value == \
        pytest.approx(s1 - s0)


def test_registry_rejects_kind_conflicts(obs_on):
    obs.counter("t_conflict")
    with pytest.raises(TypeError):
        obs.gauge("t_conflict")


# ---------------------------------------------------------------------------
# transfer counter: the PR 8 invariant, off and on
# ---------------------------------------------------------------------------

def test_tol_solve_one_transfer_obs_off(fresh_obs, monkeypatch):
    """Acceptance: telemetry disabled changes nothing — a tol solve is
    still exactly one device->host fetch, and the counter stays 0."""
    problem = _scenario_problem()
    Solver(TOL_CONF).run(problem)          # compile outside the guard
    calls = _count_device_gets(monkeypatch)
    with jax.transfer_guard_device_to_host("disallow"):
        Solver(TOL_CONF).run(problem)
    assert len(calls) == 1
    assert obs.REGISTRY.find("repro_transfers_device_to_host_total") == []


def test_tol_solve_counts_one_transfer_obs_on(obs_on, monkeypatch):
    """Acceptance: with telemetry on, the library-level counter sees the
    same single fetch the monkeypatch sees — no extra transfers appear
    because observability was enabled."""
    problem = _scenario_problem()
    Solver(TOL_CONF).run(problem)
    before = obs.counter("repro_transfers_device_to_host_total").value
    calls = _count_device_gets(monkeypatch)
    with jax.transfer_guard_device_to_host("disallow"):
        Solver(TOL_CONF).run(problem)
    after = obs.counter("repro_transfers_device_to_host_total").value
    assert len(calls) == 1
    assert after - before == 1.0
    (solves,) = obs.REGISTRY.find("repro_solves_total")
    assert solves.value >= 1.0


# ---------------------------------------------------------------------------
# plan-cache counters
# ---------------------------------------------------------------------------

def test_plan_cache_compile_counter(obs_on):
    cache = PlanCache()
    assert cache.mark_compiled(("a",)) is True
    assert cache.mark_compiled(("a",)) is False
    assert cache.mark_compiled(("b",)) is True
    assert obs.counter("repro_plan_compiles_total").value == 2.0


def test_plan_cache_lookup_counters(obs_on):
    from repro.serving.cache import Plan

    cache = PlanCache()
    key = PlanKey(structure_hash="s", loss="l", regularizer="r",
                  backend="dense", shape_sig=(1, 1, 1, 1, 1))
    cache.get_or_build(key, lambda: Plan(key=key))
    cache.get_or_build(key, lambda: Plan(key=key))
    hits = {dict(m.labels)["outcome"]: m.value
            for m in obs.REGISTRY.find("repro_plan_cache_lookups_total")}
    assert hits == {"miss": 1.0, "hit": 1.0}
    assert obs.gauge("repro_plan_cache_entries").value == 1.0


# ---------------------------------------------------------------------------
# serving events + timing split
# ---------------------------------------------------------------------------

def test_serving_stream_emits_valid_events(obs_on, tmp_path):
    events_path = str(tmp_path / "events.jsonl")
    obs.events.attach(events_path)
    service = SolveService(_serve_cfg())
    problem = _scenario_problem()
    sid = service.create_session("tenant_t", problem)
    queue = ServingQueue(service, max_batch=4, max_wait_requests=8)
    queue.submit(sid)
    queue.drain()
    queue.submit(sid)
    queue.drain()
    service.solve_path(sid, [1e-1, 1e-2])
    obs.events.LOG.close()

    n = obs_events.validate_jsonl(events_path)
    assert n == 4                          # 2 solves + 2 path points
    with open(events_path) as f:
        evs = [json.loads(line) for line in f]
    kinds = [e["event"] for e in evs]
    assert kinds == ["solve", "solve", "path", "path"]
    assert evs[0]["compiled"] and not evs[1]["compiled"]
    assert not evs[0]["warm"] and evs[1]["warm"]
    assert all(e["tenant"] == "tenant_t" for e in evs)
    roll = obs_events.rolling_latency()
    assert roll["count"] == 4.0
    assert 0.0 < roll["p99"] and roll["p99"] < float("inf")


def test_response_timing_split(obs_on):
    """A response's compile seconds are JAX's compile events inside the
    request, its solve seconds the rest of its wall clock."""
    # the first solve compiles, whatever this process ran before
    jax.clear_caches()
    service = SolveService(_serve_cfg())
    problem = _scenario_problem()
    sid = service.create_session("tenant_t", problem)
    cold = service.solve(sid)
    service.solve(sid)              # the first warm start compiles its copies
    warm = service.solve(sid)
    assert cold.compiled and cold.compiles > 0
    assert cold.compile_seconds > 0.0
    assert cold.seconds >= cold.solve_seconds > 0.0
    assert abs(cold.seconds - cold.solve_seconds
               - cold.compile_seconds) < 1e-9
    assert not warm.compiled
    assert warm.compiles == 0 and warm.compile_seconds == 0.0
    assert warm.solve_seconds == warm.seconds


def test_no_solve_is_reexecuted(fresh_obs, monkeypatch):
    """One engine run per request, however new its program: a cold
    solve, a warm one, a path sweep and a batched group each call the
    solver once."""
    from repro.api import backends
    from repro.serving import batch, solve_batch

    calls = []
    dense = backends.BACKENDS["dense"]

    def counting(*args, **kwargs):
        calls.append("dense")
        return dense(*args, **kwargs)

    monkeypatch.setitem(backends.BACKENDS, "dense", counting)
    real_many = batch.solve_many

    def counting_many(*args, **kwargs):
        calls.append("many")
        return real_many(*args, **kwargs)

    monkeypatch.setattr(batch, "solve_many", counting_many)
    service = SolveService(_serve_cfg().replace(num_iters=200, tol=1e-9))
    problem = _scenario_problem()
    sid = service.create_session("tenant_t", problem)
    first = service.solve(sid)
    assert first.compiled and calls == ["dense"]
    service.solve(sid)
    assert calls == ["dense"] * 2
    # the sweep's one backend call is its shared warm solve
    service.solve_path(sid, [1e-1, 1e-2])
    assert calls == ["dense"] * 3
    sids = [service.create_session("tenant_b", problem) for _ in range(2)]
    responses = solve_batch(service, sids)
    assert calls == ["dense"] * 3 + ["many"]
    assert responses[0].compiled and responses[0].compiles > 0
    assert responses[1].compiles == 0
    for r in responses:
        assert abs(r.seconds - r.solve_seconds - r.compile_seconds) < 1e-9
    assert responses[0].compile_seconds == responses[1].compile_seconds


def test_queue_wait_reaches_response(obs_on):
    service = SolveService(_serve_cfg())
    problem = _scenario_problem()
    sids = [service.create_session("tenant_t", problem)
            for _ in range(2)]
    queue = ServingQueue(service, max_batch=8, max_wait_requests=100,
                         max_inflight_per_tenant=8)
    t0 = queue.submit(sids[0])
    t1 = queue.submit(sids[1])
    queue.drain()
    # the first ticket waited through the second submission
    assert t0.response.queue_wait == 1
    assert t1.response.queue_wait == 0
    submits = {dict(m.labels)["outcome"]: m.value
               for m in obs.REGISTRY.find("repro_queue_submits_total")}
    assert submits["admitted"] == 2.0


# ---------------------------------------------------------------------------
# ledger gauges: finite on empty
# ---------------------------------------------------------------------------

def test_empty_ledgers_export_finite_gauges(obs_on):
    ServiceLedger(tenant="empty").export_obs()
    CommLedger.empty().export_obs()
    text = obs_export.export_json()        # allow_nan=False: raises on NaN
    snap = json.loads(text)
    by_name = {m["name"]: m for m in snap["metrics"]
               if m["kind"] == "gauge"}
    assert by_name["repro_tenant_warm_iteration_ratio"]["value"] == 1.0
    assert by_name["repro_tenant_cache_hit_rate"]["value"] == 0.0
    assert by_name["repro_federated_bytes_per_round"]["value"] == 0.0
    assert by_name["repro_federated_cumulative_bytes"]["value"] == 0.0
    # the Prometheus rendering of the same registry also validates
    obs_export.validate_prometheus(obs_export.prometheus_text())


def test_cumulative_bytes_empty_is_empty_not_nan():
    cum = CommLedger.empty().cumulative_bytes()
    assert cum.size == 0


# ---------------------------------------------------------------------------
# export validators reject bad payloads
# ---------------------------------------------------------------------------

def test_prometheus_validator_rejects_nan():
    bad = "# TYPE x gauge\nx nan\n"
    with pytest.raises(ValueError, match="non-finite"):
        obs_export.validate_prometheus(bad)


def test_prometheus_validator_rejects_sample_less_type():
    with pytest.raises(ValueError, match="no samples"):
        obs_export.validate_prometheus("# TYPE x counter\n")


def test_validate_event_rejects_missing_and_nonfinite():
    good = {"seq": 0, "event": "solve", "tenant": "t", "session": "s",
            "queue_wait": 0, "batch_width": 1, "warm": False,
            "cache_hit": True, "compiled": False, "iterations": 10,
            "residual": 1e-4, "meets_sla": True, "seconds": 0.1,
            "solve_seconds": 0.1, "compile_seconds": 0.0, "lam": 0.01,
            "tol": 1e-3}
    obs_events.validate_event(good)
    with pytest.raises(ValueError, match="missing"):
        obs_events.validate_event({k: v for k, v in good.items()
                                   if k != "residual"})
    with pytest.raises(ValueError, match="not finite"):
        obs_events.validate_event({**good, "seconds": float("nan")})
    with pytest.raises(ValueError, match="kind"):
        obs_events.validate_event({**good, "event": "bogus"})


# ---------------------------------------------------------------------------
# spans in a profile
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU profile; its result and the profile's
    ``repro.*`` host spans as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.SPAN_PREFIX):
                    s0 = int(ev.start_ns)
                    spans.append((ev.name, s0, s0 + int(ev.duration_ns),
                                  dict(ev.stats)))
    return out, spans


def _inside(spans, inner, outer):
    """Every ``inner`` span lies in some ``outer`` span."""
    outs = [(s, e) for n, s, e, _ in spans if n == outer]
    ins = [(s, e) for n, s, e, _ in spans if n == inner]
    return bool(ins) and all(any(os_ <= s and e <= oe for os_, oe in outs)
                             for s, e in ins)


@pytest.mark.parametrize("backend", ["dense", "fused"])
def test_solver_spans_in_cpu_profile(fresh_obs, tmp_path, backend):
    """Solver.run's phases are spans of the profile, nested in
    ``repro.solver_run``; the same spans are kept on the host clock."""
    problem = _scenario_problem()
    cfg = _serve_cfg().replace(num_iters=200)
    if backend == "fused":
        cfg = cfg.replace(backend="pallas", fused=True)
    Solver(cfg).run(problem)            # compile outside the profile
    result, spans = _profiled(tmp_path, lambda: Solver(cfg).run(problem))
    assert "dual_infeasibility" in result.diagnostics
    names = {n for n, *_ in spans}
    phases = ("repro.solver_setup", "repro.solver_engine",
              "repro.solver_unpack", "repro.certificate")
    assert set(phases) | {"repro.solver_run"} <= names
    for phase in phases:
        assert _inside(spans, phase, "repro.solver_run"), phase
    kept = obs.profiled_spans()
    assert [k.name for k in kept] == ["solver_setup", "solver_engine",
                                      "solver_unpack", "certificate",
                                      "solver_run"]
    assert all(k.depth == 1 for k in kept[:-1]) and kept[-1].depth == 0
    assert kept[-1].labels == {"backend": cfg.backend}


def test_service_spans_carry_request_stats(fresh_obs, tmp_path):
    """A served request's spans: ``service_solve`` carries the session
    and the session's request number, the plan lookup, warm state and
    response nest inside it, and its compiles are the response's."""
    service = SolveService(_serve_cfg())
    problem = _scenario_problem()
    sid = service.create_session("tenant_t", problem)
    service.solve(sid)
    service.solve(sid)                   # compiles the warm-start path

    def request():
        service.update_session(sid, lam=2e-2)
        return service.solve(sid)

    resp, spans = _profiled(tmp_path, request)
    (solve,) = [x for x in spans if x[0] == "repro.service_solve"]
    assert solve[3]["session"] == sid and solve[3]["request"] == 3
    assert solve[3]["compiles"] == resp.compiles
    (update,) = [x for x in spans if x[0] == "repro.service_update_session"]
    assert update[3]["session"] == sid
    for inner in ("repro.service_plan", "repro.service_warm_state",
                  "repro.service_response", "repro.solver_run"):
        assert _inside(spans, inner, "repro.service_solve"), inner
    assert _inside(spans, "repro.solver_engine", "repro.solver_run")
    kept = {k.name: k for k in obs.profiled_spans()}
    assert kept["service_solve"].depth == 0
    assert kept["service_solve"].compiles == resp.compiles
    assert kept["service_solve"].labels == {"session": sid, "request": 3}
    assert kept["service_response"].depth == 1
