"""Scalability benchmark: Algorithm 1 cost vs graph size, fused vs unfused.

The paper's computational claim (§4): applying D / D^T touches only
neighbouring nodes and edges, so the per-iteration cost is O(|V| + |E|)
— "scalable to massive collections of local datasets".  This benchmark
measures *per-iteration* throughput of the jitted solver (compile and
warmup excluded: every configuration is solved once to compile, then the
second, cache-hot solve is timed) while growing the SBM graph by ~2
orders of magnitude, and compares four execution paths:

  * ``dense``                    — lax.scan engine, no kernels,
  * ``pallas_unfused``           — the pallas backend with fusion off
                                   (on TPU: the unfused tv_prox /
                                   batched_affine kernels; off-TPU: their
                                   jnp references),
  * ``pallas_unfused_interpret`` — the unfused Pallas kernels forced
                                   through interpret mode.  Off-TPU this
                                   is the *recorded baseline*: it is what
                                   the pallas backend executed before the
                                   fused path + off-TPU fast path landed,
  * ``pallas_fused``             — the fused primal-dual kernel over the
                                   edge-blocked layout (kernel on TPU,
                                   bit-comparable jnp reference off-TPU),
  * ``federated``                — the round-based message-passing
                                   runtime in synchronous full-
                                   participation mode (one engine step
                                   per round plus the mailbox/mirror
                                   bookkeeping), the overhead price of
                                   the federated execution model.

Three device-resident-solve columns ride along (PR 8):

  * ``fused_bf16``            — the fused path under the bf16 storage /
                                f32 accumulation policy
                                (``SolverConfig.dtype="bfloat16"``),
  * ``tol_device_stop``       — a tol solve (``lax.while_loop`` over
                                metric blocks, residual carried on
                                device, one host transfer total) over
                                the cadence-matched fixed-budget scan,
  * ``path_masked_vs_dense``  — total iterations the masked-vmap
                                ``solve_path`` executes over the
                                unmasked fixed-budget sweep's
                                ``L * budget`` (measured once at a
                                fixed size; < 1 is the win).

A ``sharded_fused`` scale-out section rides along: the fused kernel
inside shard_map shards over a two-level hierarchical partition, at
sizes up to 1M nodes / 10M edges — far beyond the in-process ladder.
On a TPU host it runs in this process over every chip (a child could
not reach chips this process holds); off-TPU it runs on virtual CPU
devices in a subprocess (``--xla_force_host_platform_device_count``).
Each row reports per-shard and aggregate edge-iters/s against two
same-process references: the single-device fused path and the
single-shard (S=1) hierarchical solve, both at the matched per-shard
size.  A row whose per-shard fused window exceeds the cap (on a TPU,
the VMEM cap) is reported as skipped, not compiled.

The full run lands in ``BENCH_scaling.json`` at the repo root (plus
``results/benchmarks/scaling.json``) so subsequent PRs have a perf
trajectory to regress against; smoke runs write
``BENCH_scaling_smoke.json`` instead so CI never clobbers the committed
baseline.  ``fused_vs_unfused`` is the acceptance column (fused
throughput over the unfused-interpret pallas baseline).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from functools import partial

import numpy as np

from benchmarks.common import best_of, interleaved_best_of, save_result

SIZES = (250, 1000, 4000, 16000, 32000)
SMOKE_SIZES = (250, 1000)
ITERS = 200
SMOKE_ITERS = 40
# hierarchical scale-out column: sizes are too big for the in-process
# ladder (and need a multi-device CPU), so they run in a subprocess
SHARDED_SIZES = (250_000, 1_000_000)
SMOKE_SHARDED_SIZES = (8_000,)
SHARDED_SHARDS = 8
SMOKE_SHARDED_SHARDS = 4
SHARDED_ITERS = 5
SMOKE_SHARDED_ITERS = 20
# clustered topology for the scale-out rows: ~2000-node clusters with a
# sparse inter-cluster backbone (the paper's federated regime); the
# cross-edge budget is ~0.7% of nodes so the 1-hop halo (and its
# replicated 2nd ring) stays a small fraction of each shard
SHARDED_CLUSTER_NODES = 2000
# the masked-vs-dense lambda-path measurement runs once, at a fixed size
PATH_SIZE = 4000
SMOKE_PATH_SIZE = 250
PATH_LAMS = (1e-1, 1e-3, 6)        # np.geomspace endpoints + count
PATH_BUDGET = 4000
SMOKE_PATH_BUDGET = 1000
PATH_TOL = 5e-3
# interpret-mode emulation is orders of magnitude slower; a handful of
# iterations is plenty to time one (compile is still excluded)
ITERS_INTERPRET = 4

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(REPO_ROOT, "BENCH_scaling.json")
# smoke (CI) runs must not clobber the committed full-run baseline
BENCH_SMOKE_PATH = os.path.join(REPO_ROOT, "BENCH_scaling_smoke.json")

METHODOLOGY = (
    "Per-iteration throughput of the cache-hot jitted solve (each config "
    "is run once to compile+warm, then timed on the second run; metrics "
    "evaluated once per run via metric_every=num_iters). "
    "pallas_unfused_interpret runs the unfused tv_prox/batched_affine "
    "Pallas kernels in interpret mode over fewer iterations "
    f"({ITERS_INTERPRET}); off-TPU it is the recorded baseline — the "
    "exact execution the pallas backend used before the fused kernel and "
    "the off-TPU jnp fast path existed. fused_vs_unfused = pallas_fused "
    "/ pallas_unfused_interpret; fused_vs_unfused_fastpath = pallas_fused "
    "/ pallas_unfused (the post-PR unfused path). federated runs the "
    "message-passing runtime in synchronous full-participation mode (one "
    "engine step per round); federated_overhead = dense / federated, the "
    "per-iteration price of the mailbox/mirror protocol. fused_bf16 runs "
    "the fused path with SolverConfig.dtype='bfloat16' (bf16 storage, "
    "f32 accumulation); fused_bf16_vs_unfused_fastpath is its fastpath "
    "ratio. tol_device_stop = pallas_fused_tol / pallas_fused_cadence: "
    "an unreachable-tol while_loop solve (residual computed on device "
    "every metric block, one host transfer total) over the fixed-budget "
    "scan at the same metric cadence — the pure overhead of the "
    "device-resident stopping machinery. path_masked_vs_dense (top "
    "level, fixed size) = total iterations the masked-vmap tol "
    "solve_path executed / (num_lambdas * budget), the fraction of the "
    "unmasked fixed-budget sweep the masked sweep pays. Each mode is "
    "timed three times cache-hot and the best run is kept "
    "(benchmarks.common.best_of). obs_overhead interleaves the largest "
    "dense solve with REPRO_OBS telemetry enabled and disabled "
    "(benchmarks.common.interleaved_best_of) and reports the on/off "
    "ratio — a machine-relative gate (<= 1.02) on the telemetry stack's "
    "when-off cost; absolute seconds are never compared across machines. "
    "sharded_fused rows run the hierarchical-partition backend on "
    "multiple virtual CPU devices in a subprocess; topology is an SBM "
    "with ~2000-node clusters and a sparse inter-cluster backbone "
    "(cross edges ~ 0.7% of nodes), the regime where a cluster-aware "
    "cut keeps the halo small. On a host whose virtual devices "
    "time-share the cores, aggregate edge-iters/s equals the per-shard "
    "rate a real S-device mesh would sustain, so "
    "weak_scaling_efficiency = aggregate / single-device-fused at the "
    "matched per-shard size is the device-parallel-equivalent per-shard "
    "ratio (full-run gate >= 0.7 at the largest row); smoke runs gate "
    "per_shard_vs_single_shard >= 0.85 instead — per-shard throughput "
    "within 15% of the single-shard hierarchical baseline measured in "
    "the same run."
)


def _make_clustered(v: int, seed: int, cross_edges: float):
    """SBM with ~2000-node clusters and a sparse inter-cluster backbone
    (expected ``cross_edges`` edges across clusters) — the scale-out
    topology.  Giant-cluster SBMs are expanders: no balanced partition
    can keep their edges shard-internal, so the hierarchical rows use
    the many-cluster regime the paper targets."""
    import jax.numpy as jnp

    from repro.core import losses as L
    from repro.core.graph import sbm_graph_sparse

    rng = np.random.default_rng(seed)
    nc = max(v // SHARDED_CLUSTER_NODES, 1)
    cs = [v // nc] * nc
    cs[-1] += v - sum(cs)
    # degree ~20.5 so the 1M-node row clears 10M edges after sampling
    g, assign = sbm_graph_sparse(
        rng, tuple(cs), p_in=min(20.5 / (v / nc), 1.0),
        p_out=min(2.0 * cross_edges / (v * v), 1.0))
    w_true = np.where(assign[:, None] % 2 == 0, [2.0, 2.0],
                      [-2.0, 2.0]).astype(np.float32)
    x = rng.standard_normal((v, 5, 2)).astype(np.float32)
    y = np.einsum("vmn,vn->vm", x, w_true)
    labeled = np.zeros(v, np.float32)
    labeled[rng.choice(v, size=max(v // 10, 10), replace=False)] = 1.0
    data = L.NodeData(x=jnp.asarray(x), y=jnp.asarray(y),
                      sample_mask=jnp.ones((v, 5), jnp.float32),
                      labeled_mask=jnp.asarray(labeled))
    return g, data


def _sharded_worker(size: int, shards: int, iters: int, seed: int) -> dict:
    """Measure the hierarchical ``sharded_fused`` path on ``shards``
    devices: the chips, in the benchmark's own process, on a TPU host;
    virtual CPU devices in a subprocess elsewhere (XLA_FLAGS must be set
    before jax is imported, and the parent keeps exactly one device).

    Reports per-shard and aggregate edge-iters/s plus two references
    measured in the same process: the single-device fused path at the
    matched per-shard size, and the single-shard (S=1) hierarchical
    solve of the same per-shard-sized problem.  On a host where the
    virtual devices time-share the cores, the *aggregate* hierarchical
    throughput equals the per-shard rate an S-device mesh would sustain,
    so ``weak_scaling_efficiency`` = aggregate / single-device-matched
    is the device-parallel-equivalent per-shard ratio."""
    import time as _time

    from repro.api import Problem, Solver, SolverConfig
    from repro.api.losses import SquaredLoss
    from repro.core.distributed import (shard_problem_fused,
                                        solve_nlasso_hier)
    from repro.core.graph import fused_window_bytes, fused_window_cap
    from repro.core.mesh import make_host_mesh

    cross = 0.007 * size
    t0 = _time.perf_counter()
    g, data = _make_clustered(size, seed, cross)
    build_s = _time.perf_counter() - t0

    # plan under the fused window cap, as sharded_fused does
    nf, cap = data.num_features, fused_window_cap()
    pf = SquaredLoss().prox_param_floats(data.x.shape[1], nf)
    hint = (nf, pf, 4, cap)
    t0 = _time.perf_counter()
    sp = shard_problem_fused(g, data, shards, seed=seed, window_hint=hint)
    plan_s = _time.perf_counter() - t0
    h = sp.hier
    row = {"size": int(size), "edges": int(g.num_edges),
           "shards": int(shards), "iters": int(iters),
           "build_s": build_s, "plan_s": plan_s}
    window = fused_window_bytes(h.block_nodes, h.block_edges, h.kn, h.klo,
                                h.khi, nf, param_floats=pf)
    if window > cap:            # the chip's compiler would refuse it
        return {**row, "skipped": f"per-shard fused window {window} B > "
                                  f"VMEM cap {cap} B"}
    mesh = make_host_mesh(shards, 1)

    def time_hier():
        best = float("inf")
        for _ in range(2):
            t0 = _time.perf_counter()
            w, _, _, comm = solve_nlasso_hier(sp, mesh, 1e-3, iters)
            np.asarray(w)
            best = min(best, _time.perf_counter() - t0)
        return iters / best, comm

    _, comm = time_hier()                      # compile + warm
    its, comm = time_hier()
    aggregate = g.num_edges * its

    # single-device fused reference at the matched per-shard size
    gr, dr = _make_clustered(size // shards, seed + 1, cross / shards)
    prob = Problem.create(gr, dr, lam=1e-3)
    solver = Solver(SolverConfig(num_iters=iters, metric_every=iters,
                                 backend="pallas", fused=True))

    def time_ref():
        best = float("inf")
        for _ in range(2):
            t0 = _time.perf_counter()
            solver.run(prob).w.block_until_ready()
            best = min(best, _time.perf_counter() - t0)
        return iters / best

    time_ref()                                 # compile + warm
    ref_aggregate = gr.num_edges * time_ref()

    # single-shard hierarchical baseline at the same per-shard size (the
    # CI smoke gate is machine-relative against this)
    sp1 = shard_problem_fused(gr, dr, 1, seed=seed, window_hint=hint)
    mesh1 = make_host_mesh(1, 1)

    def time_hier1():
        best = float("inf")
        for _ in range(2):
            t0 = _time.perf_counter()
            w, _, _, _ = solve_nlasso_hier(sp1, mesh1, 1e-3, iters)
            np.asarray(w)
            best = min(best, _time.perf_counter() - t0)
        return iters / best

    time_hier1()                               # compile + warm
    hier1_aggregate = gr.num_edges * time_hier1()

    return {
        **row,
        "comm": comm,
        "cut_fraction": float(h.cut_fraction),
        "halo_nodes": int(h.halo_nodes),
        "replicated_edges": int(h.replicated_edges),
        "iters_per_s": its,
        "edge_iters_per_s": aggregate,
        "per_shard_edge_iters_per_s": aggregate / shards,
        "single_device_matched_edge_iters_per_s": ref_aggregate,
        "single_shard_matched_edge_iters_per_s": hier1_aggregate,
        "weak_scaling_efficiency": aggregate / ref_aggregate,
        "per_shard_vs_single_shard": aggregate / hier1_aggregate,
    }


def _run_sharded_rows(sizes, shards: int, iters: int, seed: int,
                      verbose: bool) -> dict:
    """One row per scale-out size.  On TPU the rows run here, over every
    chip of the host: this process holds the chips, so a child could not
    reach them.  Off-TPU each row gets a subprocess with ``shards``
    virtual CPU devices (fresh XLA_FLAGS each)."""
    import subprocess
    import sys

    import jax

    on_tpu = jax.default_backend() == "tpu"
    rows = {}
    for v in sizes:
        if on_tpu:
            rows[str(v)] = _sharded_worker(v, jax.device_count(), iters,
                                           seed)
            if verbose:
                _print_sharded_row(v, rows[str(v)])
            continue
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={shards}")
        env["PYTHONPATH"] = (REPO_ROOT + os.pathsep +
                             os.path.join(REPO_ROOT, "src") + os.pathsep +
                             env.get("PYTHONPATH", ""))
        cmd = [sys.executable, "-m", "benchmarks.scaling",
               "--sharded-worker", "--size", str(v), "--shards", str(shards),
               "--iters", str(iters), "--seed", str(seed)]
        res = subprocess.run(cmd, capture_output=True, text=True, env=env,
                             timeout=3600)
        if res.returncode != 0:
            raise RuntimeError(f"sharded worker |V|={v} failed:\n"
                               + res.stderr[-4000:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        rows[str(v)] = row
        if verbose:
            _print_sharded_row(v, row)
    return rows


def _print_sharded_row(v: int, row: dict) -> None:
    head = f"|V|={v:>8d} |E|={row['edges']:>9d} S={row['shards']} "
    if "skipped" in row:
        print(head + f"SKIPPED: {row['skipped']}")
        return
    print(head + f"comm={row['comm']} cut={row['cut_fraction']:.4f} "
          f"{row['iters_per_s']:7.3f}it/s "
          f"per-shard {row['per_shard_edge_iters_per_s']:.3e} "
          f"weak-scaling {row['weak_scaling_efficiency']:.3f}")


def _make(v: int, seed: int):
    import jax.numpy as jnp

    from repro.core import losses as L
    from repro.core.graph import sbm_graph

    rng = np.random.default_rng(seed)
    # keep expected degree ~20 so |E| grows linearly with |V|
    p_in = min(20.0 / (v / 2), 1.0)
    g, assign = sbm_graph(rng, (v // 2, v // 2), p_in=p_in, p_out=1e-4)
    w_true = np.where(assign[:, None] == 0, [2.0, 2.0],
                      [-2.0, 2.0]).astype(np.float32)
    x = rng.standard_normal((v, 5, 2)).astype(np.float32)
    y = np.einsum("vmn,vn->vm", x, w_true)
    labeled = np.zeros(v, np.float32)
    labeled[rng.choice(v, size=max(v // 10, 10), replace=False)] = 1.0
    data = L.NodeData(x=jnp.asarray(x), y=jnp.asarray(y),
                      sample_mask=jnp.ones((v, 5), jnp.float32),
                      labeled_mask=jnp.asarray(labeled))
    return g, data


def _time_iters_per_s(problem, cfg, repeats: int = 3) -> float:
    from repro.api import Solver

    solver = Solver(cfg)

    def once():
        solver.run(problem).w.block_until_ready()

    best, _ = best_of(repeats, once, warmup=1)   # warmup = compile
    return cfg.num_iters / best


def _measure_obs_overhead(problem, cfg, repeats: int = 5) -> dict:
    """Telemetry-on vs telemetry-off wall clock of the identical dense
    solve, interleaved so the *ratio* is machine-relative — the CI
    overhead gate reads ``ratio`` (<= 1.02 required), never absolute
    seconds."""
    from repro import obs
    from repro.api import Solver

    solver = Solver(cfg)

    def once():
        solver.run(problem).w.block_until_ready()

    def with_obs():
        obs.enable()
        try:
            once()
        finally:
            obs.disable()

    was_enabled = obs.enabled()
    obs.disable()
    try:
        once()                       # compile shared by both variants
        on_s, off_s = interleaved_best_of(repeats, with_obs, once)
    finally:
        (obs.enable if was_enabled else obs.disable)()
    return {"on_s": on_s, "off_s": off_s, "ratio": on_s / off_s}


def _measure_masked_path(size: int, budget: int, seed: int) -> dict:
    """Total iterations the masked tol solve_path executes vs the
    unmasked fixed-budget sweep's L * budget (iteration counts, not
    wall-clock: the masked win is *skipped work*)."""
    import jax.numpy as jnp

    from repro.api import Problem, SolverConfig
    from repro.api.solver import solve_path
    from repro.engine import capped

    g, data = _make(size, seed)
    problem = Problem.create(g, data, lam=1e-3)
    lams = np.geomspace(*PATH_LAMS)
    cfg = SolverConfig(final_iters=budget, metric_every=20, tol=PATH_TOL,
                       rho=1.9)
    t0 = time.perf_counter()
    res = solve_path(problem, jnp.asarray(lams, jnp.float32), cfg)
    wall = time.perf_counter() - t0
    iters = np.asarray(res.diagnostics["iterations"])
    eff_budget = capped(cfg.final_iters, cfg.metric_every)
    unmasked = int(len(lams) * eff_budget)
    return {
        "size": size,
        "lams": [float(l) for l in lams],
        "tol": PATH_TOL,
        "budget": int(eff_budget),
        "masked_iters": [int(i) for i in iters],
        "masked_total": int(iters.sum()),
        "unmasked_total": unmasked,
        "ratio": float(iters.sum() / unmasked),
        "wall_s": wall,
    }


def run(seed: int = 0, verbose: bool = True, smoke: bool | None = None) -> dict:
    import jax

    from repro.api import SolverConfig
    from repro.kernels.ridge_prox import batched_affine as _affine_kernel
    from repro.kernels.tv_prox import tv_prox as _tv_kernel

    if smoke is None:
        smoke = bool(os.environ.get("REPRO_SMOKE"))
    sizes = SMOKE_SIZES if smoke else SIZES
    iters = SMOKE_ITERS if smoke else ITERS

    # module-level singletons so both timed runs share one jit cache entry
    interp_hooks = dict(clip_fn=partial(_tv_kernel, interpret=True),
                        affine_fn=partial(_affine_kernel, interpret=True))

    rows = {}
    for v in sizes:
        g, data = _make(v, seed)
        from repro.api import Problem
        problem = Problem.create(g, data, lam=1e-3)

        def cfg(num_iters, **kw):
            return SolverConfig(num_iters=num_iters,
                                metric_every=num_iters, **kw)

        # metric cadence for the tol-vs-scan pair: the while_loop tol
        # engine evaluates metrics+residual per block, so its honest
        # baseline is the scan at the same cadence, not metrics-once
        me = max(iters // 10, 1)
        modes = {
            "dense": _time_iters_per_s(problem, cfg(iters)),
            "pallas_unfused": _time_iters_per_s(
                problem, cfg(iters, backend="pallas", fused=False)),
            "pallas_unfused_interpret": _time_iters_per_s(
                problem, cfg(ITERS_INTERPRET, backend="pallas",
                             fused=False, **interp_hooks)),
            "pallas_fused": _time_iters_per_s(
                problem, cfg(iters, backend="pallas", fused=True)),
            "fused_bf16": _time_iters_per_s(
                problem, cfg(iters, backend="pallas", fused=True,
                             dtype="bfloat16")),
            "pallas_fused_cadence": _time_iters_per_s(
                problem, SolverConfig(num_iters=iters, metric_every=me,
                                      backend="pallas", fused=True)),
            "pallas_fused_tol": _time_iters_per_s(
                problem, SolverConfig(num_iters=iters, metric_every=me,
                                      backend="pallas", fused=True,
                                      tol=0.0)),
            "federated": _time_iters_per_s(
                problem, cfg(iters, backend="federated")),
        }
        rows[str(v)] = {
            "edges": int(g.num_edges),
            "iters_per_s": modes,
            "edge_iters_per_s": {k: g.num_edges * r for k, r in
                                 modes.items()},
            "fused_vs_unfused": (modes["pallas_fused"]
                                 / modes["pallas_unfused_interpret"]),
            "fused_vs_unfused_fastpath": (modes["pallas_fused"]
                                          / modes["pallas_unfused"]),
            "fused_bf16_vs_unfused_fastpath": (modes["fused_bf16"]
                                               / modes["pallas_unfused"]),
            "fused_bf16_vs_f32": (modes["fused_bf16"]
                                  / modes["pallas_fused"]),
            "tol_device_stop": (modes["pallas_fused_tol"]
                                / modes["pallas_fused_cadence"]),
            "federated_overhead": modes["dense"] / modes["federated"],
        }
        if verbose:
            r = rows[str(v)]
            print(f"|V|={v:>6d} |E|={r['edges']:>8d} "
                  + " ".join(f"{k}={modes[k]:9.2f}it/s" for k in modes)
                  + f" fused_vs_unfused={r['fused_vs_unfused']:7.1f}x")

    path = _measure_masked_path(
        SMOKE_PATH_SIZE if smoke else PATH_SIZE,
        SMOKE_PATH_BUDGET if smoke else PATH_BUDGET, seed)
    if verbose:
        print(f"path_masked_vs_dense @|V|={path['size']}: "
              f"{path['masked_total']}/{path['unmasked_total']} iters "
              f"(ratio {path['ratio']:.3f}, {path['wall_s']:.1f}s)")

    # telemetry-overhead gate: the instrumented dense solve, obs on vs
    # off, at the largest size measured (problem still bound from the
    # loop above)
    obs_overhead = _measure_obs_overhead(problem, cfg(iters))
    obs_overhead["size"] = int(sizes[-1])
    obs_overhead["ok"] = bool(obs_overhead["ratio"] <= 1.02)
    if verbose:
        print(f"obs_overhead @|V|={sizes[-1]}: on/off ratio "
              f"{obs_overhead['ratio']:.4f} "
              f"({'PASS' if obs_overhead['ok'] else 'FAIL'})")

    # hierarchical scale-out rows (the chips on TPU; off-TPU a subprocess
    # on virtual CPU devices)
    sh_sizes = SMOKE_SHARDED_SIZES if smoke else SHARDED_SIZES
    sh_shards = SMOKE_SHARDED_SHARDS if smoke else SHARDED_SHARDS
    sh_iters = SMOKE_SHARDED_ITERS if smoke else SHARDED_ITERS
    sharded_rows = _run_sharded_rows(sh_sizes, sh_shards, sh_iters, seed,
                                     verbose)
    largest_sh = sharded_rows[str(sh_sizes[-1])]
    measured = "skipped" not in largest_sh
    sharded = {
        "rows": sharded_rows,
        "shards": largest_sh["shards"],
        # full-run gate: device-parallel-equivalent per-shard throughput
        # of the largest row >= 0.7x the single-device fused path at the
        # matched per-shard size; smoke gate (CI): per-shard throughput
        # within 15% of the single-shard hierarchical baseline measured
        # in the same run (machine-relative).  A skipped row (window over
        # the VMEM cap) was not measured and does not pass.
        "ok": measured and bool(
            largest_sh["per_shard_vs_single_shard"] >= 0.85 if smoke else
            largest_sh["weak_scaling_efficiency"] >= 0.7),
    }
    if verbose and not measured:
        print("sharded_fused gate: NOT MEASURED (largest row skipped)")
    elif verbose:
        print(f"sharded_fused gate: "
              f"{'PASS' if sharded['ok'] else 'FAIL'} "
              f"(weak-scaling {largest_sh['weak_scaling_efficiency']:.3f}, "
              f"vs single-shard "
              f"{largest_sh['per_shard_vs_single_shard']:.3f})")

    # near-linear gate: fused edge-throughput at the largest size within
    # 10x of its peak across sizes
    tps = [r["edge_iters_per_s"]["pallas_fused"] for r in rows.values()]
    payload = {
        "rows": rows,
        "sharded_fused": sharded,
        "path_masked_vs_dense": path,
        "obs_overhead": obs_overhead,
        "iters": iters,
        "iters_interpret": ITERS_INTERPRET,
        "smoke": bool(smoke),
        "backend": jax.default_backend(),
        "methodology": METHODOLOGY,
        "ok": bool(tps[-1] > max(tps) / 10 and sharded["ok"]),
    }
    save_result("scaling", payload)
    out_path = BENCH_SMOKE_PATH if smoke else BENCH_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    if verbose:
        print(f"near-linear gate: {'PASS' if payload['ok'] else 'FAIL'}")
        print(f"wrote {out_path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="capped sizes/iterations (CI smoke mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sharded-worker", action="store_true",
                    help="internal: measure one sharded_fused row and "
                         "print it as JSON (run with XLA_FLAGS "
                         "--xla_force_host_platform_device_count set)")
    ap.add_argument("--size", type=int, default=0)
    ap.add_argument("--shards", type=int, default=SHARDED_SHARDS)
    ap.add_argument("--iters", type=int, default=SHARDED_ITERS)
    args = ap.parse_args()
    from benchmarks.common import use_compile_cache
    use_compile_cache()
    if args.sharded_worker:
        print(json.dumps(_sharded_worker(args.size, args.shards,
                                         args.iters, args.seed),
                         default=float))
    else:
        run(seed=args.seed, smoke=args.smoke or None)
