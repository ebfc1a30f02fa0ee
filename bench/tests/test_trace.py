"""The trace reduction: busy and idle time, a kernel's device time and
the idle gaps by host span, on intervals made by hand and on a small
trace recorded on a TPU v5e."""
import os

import pytest

from bench.harness import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_op_name():
    assert trace.op_name("%fused_pd_step.7 = (f32[304,2]) custom-call("
                         "f32[304,2] %copy.69)") == "fused_pd_step"
    assert trace.op_name("%constant_dynamic-update-slice_fusion.10 = "
                         "f32[9]") == "constant_dynamic-update-slice_fusion"


def test_union_busy_and_gaps_by_span():
    busy = trace._union([(10, 20), (15, 30), (50, 60), (55, 58)])
    assert busy == [(10, 30), (50, 60)]
    t = trace.TraceSummary(
        window=(0, 100), devices=1, busy=busy, busy_s=30e-9, op_s={},
        spans=[("bench.window", 0, 100), ("bench.request", 0, 40),
               ("bench.solve", 5, 12)])
    assert t.idle_share == pytest.approx(0.7)
    assert t.busy_in(0, 100) == pytest.approx(30e-9)
    assert t.busy_in(12, 55) == pytest.approx(23e-9)
    assert t.busy_in(40, 45) == 0.0
    # gaps: [0, 10) inside bench.solve, [30, 50) and [60, 100) outside
    # every span (bench.request closes at 40, the middle of [30, 50))
    gaps = dict(t.idle_gaps())
    assert gaps["bench.solve"] == pytest.approx(10e-9)
    assert gaps["outside bench spans"] == pytest.approx(60e-9)


def test_recorded_tpu_trace():
    """0.35 s of ``sbm300.serve_tenants`` traced on a TPU v5e: three
    requests, the fused kernel in one block, the device idle most of the
    window while the host serves (trimmed to the device plane and the
    Python thread of the host plane, event names and times only)."""
    t = trace.reduce(os.path.join(DATA, "serve_tenants.xplane.pb"))
    assert t.devices == 1
    assert t.window_s == pytest.approx(0.34911148, rel=1e-6)
    assert t.busy_s == pytest.approx(0.023033059, rel=1e-6)
    assert t.idle_share == pytest.approx(0.934024, abs=1e-5)
    assert t.op_seconds("fused_pd_step") == pytest.approx(0.020528965,
                                                          rel=1e-6)
    assert t.top_ops(1)[0][0] == "fused_pd_step"
    requests = [(s, e) for n, s, e in t.spans if n == "bench.request"]
    assert len(requests) == 3
    # the kernel runs inside the programs' busy time, and the requests'
    # device time is all of the busy time but what ran between them
    assert t.op_seconds("fused_pd_step") < t.busy_s
    inside = sum(t.busy_in(s, e) for s, e in requests)
    assert 0.9 * t.busy_s < inside <= t.busy_s
    gaps = dict(t.idle_gaps())
    assert set(gaps) <= {"bench.solve", "bench.update_session",
                         "bench.request", "outside bench spans"}
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s,
                                               rel=1e-6)
    assert max(gaps, key=gaps.get) == "bench.solve"
