"""Serving benchmark: warm-started re-solve latency under an update stream.

The serving claim: a long-lived GTVMin session answering a stream of
small data deltas should re-certify (eq.-11 residual <= tol) in a small
fraction of the cold-start iteration count, because the primal/dual
state cached from the previous solve is already near the new fixed
point.  This benchmark drives a :class:`repro.serving.SolveService`
session through a synthetic drift + edge-churn stream
(``repro.serving.stream``) and, for every event, answers it twice:
warm (the service path) and cold (from zeros against the *same*
problem state), so the warm-vs-cold comparison is per-instance honest.

Reported: p50/p99/mean request latency (warm and cold), the
warm-vs-cold iteration ratio split by event kind (data-only vs
structural edge churn), plan-cache hit rate, and the per-tenant
service ledger.  A second tenant serving the same graph structure with
different data measures cross-tenant plan sharing.

The full run lands in ``BENCH_serving.json`` at the repo root (plus
``results/benchmarks/serving.json``); smoke runs write
``BENCH_serving_smoke.json`` so CI never clobbers the committed
baseline.  ``warm_cold_iter_ratio_data`` is the acceptance column
(<= 0.2 gates ``ok``: warm re-solves on small deltas within 1/5 of
cold).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np

from benchmarks.common import interleaved_best_of, save_result

NUM_STEPS = 30
SMOKE_STEPS = 6
CHURN_EVERY = 5
SMOKE_CHURN_EVERY = 3
LAM = 1e-2
BATCH_SESSIONS = 4

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(REPO_ROOT, "BENCH_serving.json")
# smoke (CI) runs must not clobber the committed full-run baseline
BENCH_SMOKE_PATH = os.path.join(REPO_ROOT, "BENCH_serving_smoke.json")

METHODOLOGY = (
    "One SolveService session per tenant (sbm_regression scenario, "
    f"lam={LAM}, tol-certified solves at the service default config) "
    "driven through a synthetic update stream: each step replaces the "
    "labels of 5% of the nodes with drifted values (noise at 5% "
    "of the label std); every "
    "churn-th step also drops one random edge and adds one random "
    "non-edge (structural event: new structure hash, dual transfer, "
    "re-plan).  Every event is answered twice — warm (cached state) "
    "then cold (from zeros, same problem state) — so iteration ratios "
    "compare identical instances.  Latencies are wall-clock per "
    "request on the cache-hot service (the first cold solve pays the "
    "XLA compile and is reported separately as compile_seconds). "
    "warm_cold_iter_ratio_* = sum(warm iters) / sum(cold iters) over "
    "data-only / structural events.  tenant_b re-serves the same graph "
    "structure with re-seeded data to measure cross-tenant plan "
    "sharing (expect cache_hit=True, compiled=False on its cold "
    "solve).  batched: N shape-matched sessions (same graph, re-seeded "
    "labels) answered warm both sequentially and as one vmapped "
    "solve_batch flush (both cache-hot; the vmapped executable's "
    "compile is paid in a warm-up flush) — throughput_gain = "
    "sequential / batched wall-clock for the same N responses.  "
    "persistence: the live plan cache is saved, a fresh SolveService "
    "loads it (structure-hash-validated) and answers a new session "
    "with zero re-plans."
)


def _shape_matched_problems(problem, num: int, seed: int) -> list:
    """``num`` copies of ``problem`` with re-seeded labels: same graph,
    same shapes — the exec-sig-matched population solve_batch vmaps."""
    import jax.numpy as jnp

    y0 = np.asarray(problem.data.y)
    scale = 0.05 * (float(np.std(y0)) or 1.0)
    probs = []
    for i in range(num):
        rng = np.random.default_rng(seed + 1000 + i)
        y = y0 + scale * rng.standard_normal(y0.shape).astype(np.float32)
        probs.append(dataclasses.replace(
            problem,
            data=dataclasses.replace(problem.data, y=jnp.asarray(y))))
    return probs


def _batched_report(problem, seed: int,
                    num_sessions: int = BATCH_SESSIONS) -> dict:
    """Sequential-vs-batched warm throughput over shape-matched sessions."""
    from repro.serving import ServingQueue, SolveService, solve_batch

    svc = SolveService()
    sids = [svc.create_session(f"tenant_batch_{i}", p)
            for i, p in enumerate(
                _shape_matched_problems(problem, num_sessions, seed))]
    for sid in sids:                  # cold: plans + singleton executable
        svc.solve(sid)

    def run_sequential():
        return [svc.solve(sid) for sid in sids]

    def run_batched():
        return solve_batch(svc, sids)

    # warm-ups: the first warm sequential round settles the session
    # state; the first flush pays the vmapped executable's compile
    run_sequential()
    run_batched()
    # interleaved best-of-5: alternating the two measurements keeps
    # machine-load drift from biasing the ratio either way
    seq = batched = None

    def timed_sequential():
        nonlocal seq
        seq = run_sequential()

    def timed_batched():
        nonlocal batched
        batched = run_batched()

    sequential_seconds, batched_seconds = interleaved_best_of(
        5, timed_sequential, timed_batched)
    gain = (sequential_seconds / batched_seconds if batched_seconds
            else float("inf"))

    # the same flush driven through the admission queue
    queue = ServingQueue(svc, max_batch=num_sessions,
                         max_wait_requests=4 * num_sessions)
    tickets = [queue.submit(sid) for sid in sids]
    queue.drain()
    return {
        "sessions": num_sessions,
        "sequential_seconds": sequential_seconds,
        "batched_seconds": batched_seconds,
        "throughput_gain": gain,
        "all_certified": bool(all(r.meets_sla for r in seq + batched)),
        "batch_iterations": batched[0].iterations,
        "queue_all_served": bool(all(t is not None and t.done
                                     for t in tickets)),
        "queue": queue.stats(),
    }


def _persistence_report(svc, problem, path: str) -> dict:
    """Save the live plan cache; a fresh service must reuse it."""
    from repro.serving import SolveService

    saved = svc.save_plans(path)
    restarted = SolveService()
    loaded = restarted.load_plans(path)
    sid = restarted.create_session("tenant_restart", problem)
    resp = restarted.solve(sid)
    return {
        "saved_plans": saved["plans"],
        "saved_rcm_orders": saved["rcm_orders"],
        "loaded_plans": loaded["plans"],
        "hash_validated": True,       # load() raises on any mismatch
        "replans": int(restarted.plans.misses),
        "restart_cache_hit": bool(resp.cache_hit),
        "restart_compiled": bool(resp.compiled),  # XLA trace still paid
        "restart_meets_sla": bool(resp.meets_sla),
    }


def run(seed: int = 0, verbose: bool = True,
        smoke: bool | None = None) -> dict:
    import jax

    from repro.scenarios import SCENARIOS
    from repro.serving import SolveService, latency_stats, replay, \
        synthetic_stream

    if smoke is None:
        smoke = bool(os.environ.get("REPRO_SMOKE"))
    num_steps = SMOKE_STEPS if smoke else NUM_STEPS
    churn_every = SMOKE_CHURN_EVERY if smoke else CHURN_EVERY

    rng = np.random.default_rng(seed)
    inst = SCENARIOS["sbm_regression"].build(seed=seed, smoke=True)
    problem = inst.problem.with_lam(LAM)

    svc = SolveService()
    sid = svc.create_session("tenant_a", problem)

    # session admission: the first solve pays plan build + XLA compile
    # (the response counts JAX's compile events inside it)
    first = svc.solve(sid)
    compile_seconds = first.compile_seconds

    events = synthetic_stream(rng, problem.data, problem.graph,
                              num_steps=num_steps,
                              drift_fraction=0.05, drift_scale=0.05,
                              churn_every=churn_every)
    records = replay(svc, sid, events, cold_reference=True)

    data_recs = [r for r in records if not r["structural"]]
    struct_recs = [r for r in records if r["structural"]]

    def iter_ratio(recs):
        warm = sum(r["warm_iterations"] for r in recs)
        cold = sum(r["cold_iterations"] for r in recs)
        return warm / cold if cold else float("nan")

    # cross-tenant plan sharing: same structure, re-seeded data
    inst_b = SCENARIOS["sbm_regression"].build(seed=seed, smoke=True)
    sid_b = svc.create_session("tenant_b", inst_b.problem.with_lam(LAM))
    resp_b = svc.solve(sid_b)

    # batched multi-session throughput + queue-driven flush
    batched = _batched_report(inst_b.problem.with_lam(LAM), seed)

    # cross-process plan persistence (restart simulation)
    plans_dir = os.path.join(REPO_ROOT, "results", "benchmarks",
                             "serving_plans")
    persistence = _persistence_report(svc, inst_b.problem.with_lam(LAM),
                                      plans_dir)

    ratio_data = iter_ratio(data_recs)
    payload = {
        "scenario": "sbm_regression",
        "lam": LAM,
        "tol": svc.config.tol,
        "num_steps": num_steps,
        "churn_every": churn_every,
        "compile_seconds": compile_seconds,
        "cold_start_iterations": first.iterations,
        "latency_warm": latency_stats(records, "warm_seconds"),
        "latency_cold": latency_stats(records, "cold_seconds"),
        "warm_cold_iter_ratio_data": ratio_data,
        "warm_cold_iter_ratio_structural": iter_ratio(struct_recs),
        "sla_met_fraction": float(np.mean(
            [r["warm_meets_sla"] for r in records])),
        "max_warm_residual": float(max(
            r["warm_residual"] for r in records)),
        "cross_tenant_plan_hit": bool(resp_b.cache_hit
                                      and not resp_b.compiled),
        "batched": batched,
        "persistence": persistence,
        "records": records,
        "service": svc.summary(),
        "smoke": bool(smoke),
        "backend": jax.default_backend(),
        "methodology": METHODOLOGY,
        "ok": bool(ratio_data <= 0.2 and resp_b.cache_hit
                   and batched["throughput_gain"] >= 2.0
                   and batched["all_certified"]
                   and persistence["replans"] == 0
                   and persistence["restart_cache_hit"]),
    }
    save_result("serving", payload)
    out_path = BENCH_SMOKE_PATH if smoke else BENCH_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    if verbose:
        lw, lc = payload["latency_warm"], payload["latency_cold"]
        print(f"cold start: {first.iterations} iters, "
              f"{first.seconds:.2f}s total "
              f"({compile_seconds:.2f}s compile)")
        print(f"warm latency  p50={lw['p50'] * 1e3:7.1f}ms "
              f"p99={lw['p99'] * 1e3:7.1f}ms")
        print(f"cold latency  p50={lc['p50'] * 1e3:7.1f}ms "
              f"p99={lc['p99'] * 1e3:7.1f}ms")
        print(f"warm/cold iterations: data-only={ratio_data:.3f} "
              f"structural={payload['warm_cold_iter_ratio_structural']:.3f}")
        print(f"SLA met on {payload['sla_met_fraction']:.0%} of requests "
              f"(max residual {payload['max_warm_residual']:.2e}, "
              f"tol {svc.config.tol})")
        print(f"cross-tenant plan hit: {payload['cross_tenant_plan_hit']}")
        print(f"batched {batched['sessions']} sessions: "
              f"seq={batched['sequential_seconds'] * 1e3:.1f}ms "
              f"batched={batched['batched_seconds'] * 1e3:.1f}ms "
              f"gain={batched['throughput_gain']:.2f}x")
        print(f"persistence: saved={persistence['saved_plans']} plans, "
              f"restart re-plans={persistence['replans']}, "
              f"cache_hit={persistence['restart_cache_hit']}")
        print(f"acceptance gate (ratio <= 0.2, batch gain >= 2x, "
              f"0 re-plans): {'PASS' if payload['ok'] else 'FAIL'}")
        print(f"wrote {out_path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short stream (CI smoke mode)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from benchmarks.common import use_compile_cache
    use_compile_cache()
    run(seed=args.seed, smoke=args.smoke or None)
