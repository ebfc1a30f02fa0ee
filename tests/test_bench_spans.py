"""The benchmark's readers of the program's own spans
(``bench/harness/program_spans.py`` and the metrics ``loop_device_ms``,
``call_device_ms``, ``solver_host_ms``, ``request_compiles``): on spans
and device intervals made by hand, on a served request profiled on the
CPU, and against a program that keeps no spans."""
import os
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.harness import program_spans, record, trace  # noqa: E402
from bench.harness.spec import Spec  # noqa: E402
from repro import obs  # noqa: E402

NEW_METRICS = ("loop_device_ms", "call_device_ms", "solver_host_ms",
               "request_compiles")


def _metric(name):
    return Spec(ROOT).module("metrics", name).read


def _run(events, busy, spans=()):
    """A one-second window: host seconds map to trace nanoseconds times
    1e9; ``busy`` in seconds, ``spans`` the benchmark's own."""
    ns = [(int(s * 1e9), int(e * 1e9)) for s, e in busy]
    summary = trace.TraceSummary(
        window=(0, 10**9), devices=1, busy=ns,
        busy_s=sum(e - s for s, e in ns) * 1e-9, op_s={},
        spans=[("bench.window", 0, 10**9)] + [
            (n, int(s * 1e9), int(e * 1e9)) for n, s, e in spans])
    return record.Run(
        config={}, traffic={}, setup_s=1.0, window=(0.0, 1.0),
        events=[record.Event(s, e, its, True) for s, e, its in events],
        num_edges=10, cost=None, device_kind="TPU v5 lite",
        trace=summary)


def _kept(monkeypatch, spans):
    """The program keeps ``spans``: (name, start, end, depth, compiles,
    labels), host seconds."""
    recs = [obs.SpanRecord(n, s, e, d, c, lab)
            for n, s, e, d, c, lab in spans]
    monkeypatch.setattr(obs, "profiled_spans", lambda: list(recs))


def test_offline_metrics_split_the_window(monkeypatch):
    """Two calls of 100 iterations; the device runs 0.3 s inside each
    engine span and 0.15 s in all outside them."""
    _kept(monkeypatch, [
        ("solver_setup", 0.01, 0.1, 1, 0, {}),
        ("solver_engine", 0.1, 0.4, 1, 0, {}),
        ("solver_run", 0.0, 0.5, 0, 0, {"backend": "pallas"}),
        ("solver_engine", 0.6, 0.9, 1, 0, {}),
        ("solver_run", 0.5, 1.0, 0, 0, {"backend": "pallas"}),
        ("solver_run", 1.5, 2.0, 0, 0, {}),          # after the window
    ])
    run = _run([(0.0, 0.5, 100), (0.5, 1.0, 100)],
               busy=[(0.05, 0.4), (0.42, 0.47), (0.55, 0.9)])
    loop = _metric("loop_device_ms")(run)
    call = _metric("call_device_ms")(run)
    assert loop == pytest.approx(1e3 * 0.6 / 200)
    assert call == pytest.approx(1e3 * (0.75 - 0.6) / 2)
    # together they are all of the window's device time, which is what
    # iter_device_ms divides by the iterations
    iter_ms = _metric("iter_device_ms")(run)
    assert loop * 200 + call * 2 == pytest.approx(iter_ms * 200)
    # a serving metric finds no service span here
    assert _metric("solver_host_ms")(run) is None
    assert _metric("request_compiles")(run) is None


def test_serve_metrics_read_top_level_service_spans(monkeypatch):
    """Two requests; host time is what the top-level service spans hold
    while the device runs nothing, and the compiles are each
    ``service_solve``'s."""
    _kept(monkeypatch, [
        ("service_update_session", 0.0, 0.05, 0, 0, {"session": "s"}),
        ("service_plan", 0.06, 0.07, 1, 0, {}),
        ("solver_engine", 0.1, 0.3, 2, 0, {}),
        ("service_response", 0.3, 0.4, 1, 0, {}),
        ("service_solve", 0.05, 0.45, 0, 2, {"session": "s",
                                             "request": 1}),
        ("service_update_session", 0.5, 0.6, 0, 1, {"session": "s"}),
        ("service_solve", 0.6, 0.95, 0, 0, {"session": "s",
                                            "request": 2}),
    ])
    run = _run([(0.0, 0.5, 150), (0.5, 1.0, 200)],
               busy=[(0.1, 0.35), (0.65, 0.9)])
    host = _metric("solver_host_ms")(run)
    # spans: 0.05 + 0.4 + 0.1 + 0.35 s, device busy 0.25 + 0.25 s inside
    assert host == pytest.approx(1e3 * (0.9 - 0.5) / 2)
    # at most the requests' own idle time (host_ms_per_request)
    assert host <= _metric("host_ms_per_request")(run) + 1e-9
    assert _metric("request_compiles")(run) == pytest.approx(1.0)
    assert _metric("loop_device_ms")(run) == pytest.approx(
        1e3 * 0.2 / 150)


def test_idle_gaps_by_innermost_program_span():
    """A gap is named by the innermost span open at its middle, a
    program span inside a benchmark span winning; a gap inside no span
    keeps its label."""
    summary = trace.TraceSummary(
        window=(0, 100), devices=1, busy=[(20, 30), (60, 70)],
        busy_s=20e-9, op_s={},
        spans=[("bench.window", 0, 100), ("bench.request", 0, 80),
               ("bench.solve", 10, 80)])
    spans = [program_spans.Span("service_solve", 10, 80, 0, {}),
             program_spans.Span("service_plan", 12, 18, 1, {}),
             program_spans.Span("service_response", 40, 55, 1, {})]
    gaps = dict(program_spans.idle_gaps(summary, spans))
    # [0, 20): middle 10 opens service_solve (start 10) -> innermost;
    # [30, 60): middle 45 in service_response; [70, 100): middle 85
    # is outside every span
    assert gaps == pytest.approx({
        "repro.service_solve": 20e-9, "repro.service_response": 30e-9,
        "outside bench spans": 30e-9})
    # the benchmark's own reduction is unchanged by the reader
    assert dict(summary.idle_gaps()) == pytest.approx({
        "bench.solve": 50e-9, "outside bench spans": 30e-9})


def test_no_program_spans_read_nothing(monkeypatch):
    """A program that keeps no spans (one older than ``profiled_spans``)
    and an untraced run give every new metric nothing to read."""
    run = _run([(0.0, 1.0, 100)], busy=[(0.1, 0.9)])
    monkeypatch.delattr(obs, "profiled_spans")
    for name in NEW_METRICS:
        assert _metric(name)(run) is None, name
    monkeypatch.undo()
    run.trace = None
    for name in NEW_METRICS:
        assert _metric(name)(run) is None, name


def test_served_request_profiled_on_cpu(tmp_path):
    """A served request under a CPU profile: the metrics read the spans
    the program kept, with its compiles those of its response."""
    from repro.api import SolverConfig
    from repro.scenarios import get_scenario
    from repro.serving import SolveService

    obs.reset()
    problem = get_scenario("sbm_regression").build(
        seed=0, smoke=True, lam=1e-2).problem
    service = SolveService(SolverConfig(num_iters=500, rho=1.9,
                                        metric_every=25, tol=1e-3,
                                        record_residual=True))
    sid = service.create_session("t", problem)
    service.solve(sid)
    service.solve(sid)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t0 = time.perf_counter()
        service.update_session(sid, lam=2e-2)
        resp = service.solve(sid)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    run = _run([(0.0, 1.0, resp.iterations)], busy=[])
    run.window = (t0, t1)
    kept = program_spans.from_program(run)
    top = [s for s in kept if s.depth == 0]
    assert [s.name for s in top] == ["service_update_session",
                                     "service_solve"]
    # no device intervals here, so all of the top spans is host time
    assert _metric("solver_host_ms")(run) == pytest.approx(
        sum(s.end - s.start for s in top) * 1e-6)
    assert _metric("request_compiles")(run) == resp.compiles
    # the same spans, read back from the profile itself
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    names = [(s.name, s.depth) for s in program_spans.from_xplane(path)]
    assert sorted(names) == sorted((s.name, s.depth) for s in kept)
    obs.reset()


def test_recorded_serve_delta_trace():
    """Two requests of ``lattice512.serve_delta`` traced on a TPU v5e
    (trimmed to the device's XLA Modules / Ops lines and the Python
    thread's ``bench.*`` and ``repro.*`` spans, the latter with their
    stats): the program's spans nest as the code nests them, carry the
    session, the request number and the compiles, and split the idle
    time; the serving metrics read from them."""
    path = os.path.join(ROOT, "bench", "tests", "data",
                        "serve_delta.xplane.pb")
    t = trace.reduce(path)
    spans = program_spans.from_xplane(path)
    assert t.window_s == pytest.approx(4.084539738, rel=1e-9)
    assert t.busy_s == pytest.approx(4.01538191, rel=1e-9)
    depth = {"service_update_session": 0, "service_solve": 0,
             "service_plan": 1, "service_warm_state": 1, "solver_run": 1,
             "service_response": 1, "solver_setup": 2, "solver_engine": 2,
             "solver_unpack": 2, "certificate": 2}
    assert sorted((s.name, s.depth) for s in spans) == sorted(
        (n, d) for n, d in depth.items() for _ in range(2))
    solves = [s for s in spans if s.name == "service_solve"]
    assert [s.stats for s in solves] == [
        {"session": "tenant0/824145f4066f", "request": r, "compiles": 0}
        for r in (4, 5)]
    # each request's phases lie inside its service_solve, in order
    for solve in solves:
        inner = sorted((s.start, s.name) for s in spans
                       if s.depth == 1 and solve.start <= s.start
                       and s.end <= solve.end)
        assert [n for _, n in inner] == ["service_plan",
                                         "service_warm_state",
                                         "solver_run", "service_response"]
    requests = [(s, e) for n, s, e in t.spans if n == "bench.request"]
    assert len(requests) == 2
    host = program_spans.solver_host_ms(t, spans, len(requests))
    assert host == pytest.approx(27.46878, rel=1e-6)
    # inside the requests' own idle time
    outer = [(e - s) * 1e-9 - t.busy_in(s, e) for s, e in requests]
    assert host <= 1e3 * sum(outer) / len(outer)
    assert program_spans.request_compiles(spans) == 0
    gaps = dict(program_spans.idle_gaps(t, spans))
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s,
                                               rel=1e-6)
    assert max(gaps, key=gaps.get) == "repro.certificate"
    assert gaps["repro.certificate"] == pytest.approx(0.019070293,
                                                      rel=1e-6)
    # the engine span holds the kernel: all of fused_pd_step's time
    engine = [s for s in spans if s.name == "solver_engine"]
    assert sum(t.busy_in(s.start, s.end) for s in engine) > \
        t.op_seconds("fused_pd_step")
