#!/usr/bin/env python3
"""Bring-up check on a TPU: the solver's main paths, at real size.

    python chip_smoke.py              # one chip: phases A-D
    python chip_smoke.py --chips 4    # four chips: the sharded backends
                                      # against a single-chip dense solve

Everything runs in this one process, through the entry points a user
calls (``Problem`` -> ``Solver.run``, ``SolveService``), with data made
from ``--seed``:

  A  ``dense`` on the clustered SBM of ``benchmarks/scaling.py`` at
     250,000 nodes (~2.55M edges, the paper's n = 2 features and m = 5
     samples per node): a fixed-iteration solve, then a ``tol`` solve
     whose eq.-11 residual must reach ``tol``.  1M nodes belongs to the
     four-chip phase (the ``A.build`` line says why).
  B  ``pallas`` on A's problem at A's fixed iteration count, final ``w``
     within 1e-4 of A's.  The RCM banding fails on this family, so the
     fused window does not fit and the unfused kernels run (the route is
     printed).
  C  the fused kernel on a 512 x 512 lattice (``grid2d``'s data model;
     multi-block banded layout) and on the paper's §5 SBM (one block,
     iterations and the residual inside the kernel): fixed-iteration
     solves within 1e-4 of ``dense`` at the same count, a ``tol`` solve
     on the single block, and every fused program holding the kernel
     (``tpu_custom_call``).
  D  ``SolveService`` on the lattice: a cold ``tol`` solve (the banded
     fused route's certificate), a data delta and a warm solve (fewer
     iterations), an edge patch and a solve; every response certified
     (``meets_sla``).  Warm starts donate their buffers on TPU.

``--chips 4`` runs only the four-chip phase: ``sharded`` on the 1M SBM
and ``sharded_fused`` on a 1024 x 1024 lattice, each over a mesh of all
four chips and within 1e-4 of a single-chip ``dense`` solve.

Every line but the last reports one step (seconds of compile, as JAX
reports it, and of the rest of the wall apart; sizes, iterations,
residuals, routes, max |dw|).  The last line
is ``{"ok": true, "device": {...}}``.  The script exits non-zero, and
prints no such line, when JAX finds no TPU or any check fails.  JAX's
persistent compilation cache is on (``benchmarks.common.use_compile_cache``),
so a second run on the same machine compiles less.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
IR_DIR = os.path.join(ROOT, ".jax_ir")        # lowered programs, phase C

SBM_NODES = 250_000
FOUR_CHIP_SBM_NODES = 1_000_000
# measured on one v5e: at 1M nodes a `dense` tol solve ran 426 s, and
# `pallas` takes the unfused route, whose lane-padded (E, 2) operands
# need 22.3 GB of HBM
SBM_WHY = "1M_dense_tol_run_426s_and_unfused_pallas_needs_22.3GB_HBM"
LATTICE_SIDE = 512
FOUR_CHIP_LATTICE_SIDE = 1024
FIXED_ITERS = 200
# the four-chip comparisons run a fixed count too; fewer iterations
# because four chips cost four times as much per second
FOUR_CHIP_ITERS = 50
BOUND = 1e-4                    # the conformance matrix's max |dw| bound


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(step: str, **fields) -> None:
    print(step + " " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def max_diff(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


# seconds JAX reports spending on tracing, lowering and compiling (its
# own compile-duration events), summed since the listener was installed
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_seconds = [0.0]


def _on_duration(event: str, seconds: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_seconds[0] += seconds


def solve_timed(solver, problem):
    """One solve; returns (result, compile_s, run_s): the compile
    seconds JAX reports during the call, and the rest of its wall."""
    import jax
    c0, t0 = _compile_seconds[0], time.perf_counter()
    res = solver.run(problem)
    jax.block_until_ready(res.w)
    wall = time.perf_counter() - t0
    comp = _compile_seconds[0] - c0
    return res, comp, wall - comp


@contextlib.contextmanager
def device_bytes_held(devices, out: list):
    """Fill ``out`` with each device's peak ``bytes_in_use`` above its
    level at entry, sampled every 10 ms while the block runs: what one
    solve holds on each device.  (``peak_bytes_in_use`` is a maximum
    over the whole process, so it cannot tell one solve's shards from an
    earlier solve's.)"""
    def level():
        return [d.memory_stats()["bytes_in_use"] for d in devices]

    base = level()
    peak = list(base)
    done = threading.Event()

    def watch():
        while not done.is_set():
            peak[:] = map(max, peak, level())
            done.wait(0.01)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    try:
        yield
    finally:
        done.set()
        watcher.join()
        out[:] = [p - b for p, b in zip(peak, base)]


@contextlib.contextmanager
def lowered_programs(tag: str):
    """Collect the StableHLO of every program lowered inside the block
    (file name -> text)."""
    import jax
    out = os.path.join(IR_DIR, tag)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    jax.config.update("jax_dump_ir_to", out)
    found: dict[str, str] = {}
    try:
        yield found
    finally:
        jax.config.update("jax_dump_ir_to", "")
        for name in sorted(os.listdir(out)):
            with open(os.path.join(out, name)) as f:
                found[name] = f.read()


def check_kernel_lowered(found: dict, tag: str) -> None:
    engines = {k: v for k, v in found.items() if "_fused_" in k}
    check(bool(engines), f"{tag}: no fused engine program was lowered")
    check(all("tpu_custom_call" in v for v in engines.values()),
          f"{tag}: a fused engine program lowered without the kernel")


def certified(res, tol: float, budget: int) -> tuple[int, float]:
    iters = int(res.diagnostics["iterations"])
    resid = float(res.residual[-1])
    check(resid <= tol and iters < budget,
          f"tol solve not certified: residual {resid} after {iters} "
          f"iterations (tol {tol}, budget {budget})")
    return iters, resid


def route_fields(res) -> dict:
    r = res.diagnostics["route"]
    keys = ("fused", "block_nodes", "num_blocks", "kn", "klo", "khi",
            "window_bytes", "window_cap")
    return {k: r[k] for k in keys if k in r}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def clustered_sbm(nodes: int, seed: int):
    from benchmarks.scaling import _make_clustered
    from repro.api import Problem
    t0 = time.perf_counter()
    g, data = _make_clustered(nodes, seed, 0.007 * nodes)
    build = time.perf_counter() - t0
    return Problem.create(g, data, lam=1e-3), build


def phase_ab(seed: int) -> None:
    """A (dense fixed + tol) and B (pallas at A's fixed iteration count)
    on one clustered SBM."""
    from repro.api import Solver, SolverConfig
    problem, build = clustered_sbm(SBM_NODES, seed)
    g = problem.graph
    say("A.build", nodes=g.num_nodes, edges=g.num_edges,
        max_degree=g.max_degree, seconds=build, why_not_1m=SBM_WHY)
    cfg = SolverConfig(num_iters=FIXED_ITERS, metric_every=FIXED_ITERS,
                       rho=1.9)
    dense, comp, run = solve_timed(Solver(cfg), problem)
    obj = float(dense.objective[-1])
    check(obj == obj and abs(obj) < float("inf"), "A: objective not finite")
    say("A.dense_fixed", iters=FIXED_ITERS, compile_s=comp, run_s=run,
        objective=obj)
    tol, budget = 1e-2, 5000
    res, comp, run = solve_timed(Solver(cfg.replace(
        num_iters=budget, metric_every=50, tol=tol)), problem)
    iters, resid = certified(res, tol, budget)
    say("A.dense_tol", tol=tol, iters=iters, residual=resid,
        dual_infeasibility=float(res.diagnostics["dual_infeasibility"]),
        compile_s=comp, run_s=run)
    pallas, comp, run = solve_timed(
        Solver(cfg.replace(backend="pallas")), problem)
    diff = max_diff(pallas.w, dense.w)
    say("B.route", **route_fields(pallas))
    say("B.pallas_fixed", iters=FIXED_ITERS, compile_s=comp, run_s=run,
        max_dw_vs_A=diff)
    check(diff <= BOUND, f"B: pallas vs dense max |dw| {diff} > {BOUND}")


def fused_fixed(tag: str, problem) -> None:
    """Fused vs dense at FIXED_ITERS: within BOUND, and the kernel in
    every fused program."""
    from repro.api import Solver, SolverConfig
    cfg = SolverConfig(backend="pallas", num_iters=FIXED_ITERS,
                       metric_every=FIXED_ITERS, rho=1.9)
    with lowered_programs(f"{tag}_fixed") as found:
        fres, comp, run = solve_timed(Solver(cfg), problem)
    check_kernel_lowered(found, f"{tag}_fixed")
    check(fres.diagnostics["route"]["fused"], f"{tag}: not fused")
    say(f"C.{tag}.route", **route_fields(fres))
    dres, dcomp, drun = solve_timed(Solver(cfg.replace(backend="dense")),
                                    problem)
    diff = max_diff(fres.w, dres.w)
    say(f"C.{tag}.fused_fixed", iters=FIXED_ITERS, compile_s=comp,
        run_s=run, dense_compile_s=dcomp, dense_run_s=drun, max_dw=diff)
    check(diff <= BOUND, f"{tag}: fused vs dense max |dw| {diff} > {BOUND}")


def lattice_problem(side: int, seed: int):
    import numpy as np

    from repro.api import Problem
    from repro.scenarios.zoo import lattice_dataset
    ds = lattice_dataset(np.random.default_rng(seed), side)
    return Problem.create(ds.graph, ds.data, lam=5e-2), ds


def phase_c(seed: int) -> None:
    from repro.api import Solver, SolverConfig
    from repro.scenarios import get_scenario
    t0 = time.perf_counter()
    problem, _ = lattice_problem(LATTICE_SIDE, seed)
    say("C.lattice.build", nodes=problem.num_nodes,
        edges=problem.graph.num_edges, seconds=time.perf_counter() - t0)
    fused_fixed("lattice", problem)
    inst = get_scenario("sbm_regression").build(seed=seed)
    say("C.sbm5.build", nodes=inst.problem.num_nodes,
        edges=inst.problem.graph.num_edges)
    fused_fixed("sbm5", inst.problem)
    tol, budget = 1e-3, 20000
    with lowered_programs("sbm5_tol") as found:
        res, comp, run = solve_timed(Solver(SolverConfig(
            backend="pallas", num_iters=budget, metric_every=50, rho=1.9,
            tol=tol)), inst.problem)
    check_kernel_lowered(found, "sbm5_tol")
    check(res.diagnostics["route"]["fused"], "sbm5 tol: not fused")
    iters, resid = certified(res, tol, budget)
    say("C.sbm5.fused_tol", tol=tol, iters=iters, residual=resid,
        compile_s=comp, run_s=run)


def phase_d(seed: int) -> None:
    import numpy as np

    from repro.api import SolverConfig
    from repro.serving import DataDelta, EdgePatch, SolveService

    problem, ds = lattice_problem(LATTICE_SIDE, seed)
    tol, budget = 1e-3, 10000
    svc = SolveService(SolverConfig(backend="pallas", num_iters=budget,
                                    metric_every=50, rho=1.9, tol=tol))
    sid = svc.create_session("smoke", problem)
    rng = np.random.default_rng(seed + 1)

    def report(step, resp):
        say(f"D.{step}", iters=resp.iterations, residual=resp.residual,
            meets_sla=resp.meets_sla, warm=resp.warm,
            certificate_keys=",".join(sorted(resp.certificate)),
            seconds=resp.seconds, compile_s=resp.compile_seconds,
            solve_s=resp.solve_seconds)
        check(resp.meets_sla and resp.certificate,
              f"D.{step}: response not certified")

    with lowered_programs("lattice_tol") as found:
        cold = svc.solve(sid)
    check_kernel_lowered(found, "lattice_tol")
    report("cold", cold)
    # fresh measurements at 1% of the nodes
    nodes = np.sort(rng.choice(problem.num_nodes, problem.num_nodes // 100,
                               replace=False))
    x = np.asarray(problem.data.x)[nodes]
    y = (np.einsum("kmn,kn->km", x, np.asarray(ds.w_true)[nodes])
         + 0.1 * rng.standard_normal(x.shape[:2])).astype(np.float32)
    svc.update_session(sid, delta=DataDelta(nodes=tuple(nodes.tolist()),
                                            y=y))
    warm = svc.solve(sid)
    report("warm_after_delta", warm)
    check(warm.warm and warm.iterations < cold.iterations,
          f"D: warm solve took {warm.iterations} iterations, cold "
          f"{cold.iterations}")
    side = LATTICE_SIDE
    add = tuple((int(i), int(i) + side + 1, 1.0)
                for i in rng.choice(side * (side - 1), 16, replace=False)
                if int(i) % side != side - 1)
    src, dst = (np.asarray(problem.graph.src),
                np.asarray(problem.graph.dst))
    drop_ids = rng.choice(problem.graph.num_edges, 16, replace=False)
    drop = tuple((int(src[e]), int(dst[e])) for e in drop_ids)
    svc.update_session(sid, patch=EdgePatch(add=add, drop=drop))
    patched = svc.solve(sid)
    report("after_edge_patch", patched)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_four(seed: int) -> None:
    import jax

    from repro.api import Solver, SolverConfig
    from repro.core.mesh import make_device_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    mesh = make_device_mesh()
    say("F.mesh", shape=dict(mesh.shape),
        devices=",".join(str(d.id) for d in mesh.devices.flat))
    cfg = SolverConfig(num_iters=FOUR_CHIP_ITERS,
                       metric_every=FOUR_CHIP_ITERS, rho=1.9)

    def sbm():
        return clustered_sbm(FOUR_CHIP_SBM_NODES, seed)[0]

    def lattice():
        return lattice_problem(FOUR_CHIP_LATTICE_SIDE, seed)[0]

    # one line per step, so a run cut short shows how far it got
    for backend, make in (("sharded", sbm), ("sharded_fused", lattice)):
        t0 = time.perf_counter()
        problem = make()
        say(f"F.{backend}.build", nodes=problem.num_nodes,
            edges=problem.graph.num_edges,
            seconds=time.perf_counter() - t0)
        dense, comp, run = solve_timed(Solver(cfg), problem)
        say(f"F.{backend}.dense", iters=FOUR_CHIP_ITERS, compile_s=comp,
            run_s=run)
        held = []
        with device_bytes_held(devices, held):
            res, comp, run = solve_timed(
                Solver(cfg.replace(backend=backend)), problem)
        diff = max_diff(res.w, dense.w)
        say(f"F.{backend}", iters=FOUR_CHIP_ITERS, compile_s=comp,
            run_s=run, max_dw=diff,
            halo_bytes_per_iter=res.diagnostics[
                "halo_exchange_bytes_per_iter"])
        check(diff <= BOUND,
              f"{backend} vs single-chip dense max |dw| {diff} > {BOUND}")
        # bytes each device held during this backend's solve alone
        say(f"F.{backend}.device_peak_bytes",
            **{f"dev{d.id}": b for d, b in zip(devices, held)})
        check(all(b > 0 for b in held),
              f"{backend}: a device held no shard during the solve")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    from benchmarks.common import use_compile_cache
    cache = use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(jax.devices()), jax=jax.__version__, cache=cache)

    phases = ((("F", phase_four),) if args.chips == 4 else
              (("AB", phase_ab), ("C", phase_c), ("D", phase_d)))
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(args.seed)
        say(f"{name}.done", seconds=time.perf_counter() - t0)
    say("total", seconds=time.perf_counter() - t_all,
        compile_s=_compile_seconds[0])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
