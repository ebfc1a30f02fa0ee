"""One run of one cell: set-up, the measured window, the check, the
metrics, and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import time

import numpy as np

from bench.harness import compare, control, cost, device, record, system
from bench.harness.spec import Spec


@dataclasses.dataclass
class Context:
    """What a loop is given: the cell's entries, the deployment built
    from the seed, the reference, and the run's switches."""

    config: dict
    traffic: dict
    seed: int
    deployment: object
    reference: object
    trace: bool
    # the precision the program runs in: the configuration's, or the
    # control's lower one
    dtype: str
    log: object = print

    def span(self, name: str):
        """A host span ``bench.<name>`` in the profiler's trace (nothing
        when the run is not traced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def sample(self, n: int, k: int, include=()) -> list[int]:
        """Up to ``k`` of ``range(n)`` drawn from the seed, with the
        indices in ``include`` among them, in order."""
        rng = np.random.default_rng([self.seed, 2])
        rest = [i for i in range(n) if i not in set(include)]
        take = max(min(k - len(include), len(rest)), 0)
        picked = rng.choice(len(rest), size=take, replace=False) \
            if take else []
        return sorted(set(include) | {rest[i] for i in picked})


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             control_run: bool = False, trace_dir: str | None = None,
             log=print) -> dict:
    """Run ``workload`` once; returns the result object (the driver's
    keys and, last, ``checks``: each compared number with its limit).
    ``control_run`` runs the cell's control in the program's place
    (:mod:`bench.harness.control`) instead."""
    spec = Spec(root)
    cell = spec.cell(workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    limits = spec.limits(workload)
    import jax
    devices = (device.accelerator_devices(cell["chips"]) if require_chip
               else jax.devices()[:cell["chips"]])
    info = device.device_info(devices)
    log(f"device platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']}")
    cache = device.use_compile_cache(root) if require_chip else None
    system.import_program(root)
    seed = int(seed) % (1 << 63)

    low = control.program_dtype(limits["control"])
    with device.CompileCounter() as counter:
        dep = spec.module("families", cfg["family"]).build(
            cfg, np.random.default_rng(seed))
        ref = spec.module("references", cfg["reference"]).Reference(
            dep.edges, dep.weights, dep.num_nodes, cfg["lam"], cfg["rho"])
        ctx = Context(config=cfg, traffic=mix,
                      seed=seed, deployment=dep, reference=ref,
                      trace=trace,
                      dtype=low if control_run else cfg["dtype"],
                      log=lambda msg: log(
                          f"{msg} at {time.perf_counter() - t_start:.1f} s"))
        loop = spec.module("loops", mix["loop"])
        state = loop.setup(ctx)
        setup_compiles = counter.snapshot()
        logdir = None
        if trace:
            logdir = trace_dir or os.path.join(
                root, ".bench_trace", f"{workload}.{seed}")
            shutil.rmtree(logdir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # no per-call Python events
            jax.profiler.start_trace(logdir, profiler_options=options)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        try:
            with ctx.span("window"):
                events = loop.window(ctx, state, t0 + seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        t1 = max(e.end for e in events)
        window_compiles = [a - b for a, b in
                           zip(counter.snapshot(), setup_compiles)]
    log(f"setup seconds={setup_s} compiles={setup_compiles[0]} "
        f"traces={setup_compiles[1]} compile_s={counter.compile_s} "
        f"cache={cache}")
    its = sorted(e.iterations for e in events)
    log(f"window seconds={t1 - t0} events={len(events)} "
        f"compiles={window_compiles[0]} traces={window_compiles[1]} "
        f"iterations min={its[0]} median={its[len(its) // 2]} "
        f"max={its[-1]}")
    info = device.device_info(devices)

    answers = loop.answers(ctx, state, events)
    del state
    gc.collect()
    correct, checks = compare.judge(loop.check(ctx, answers),
                                    limits["numbers"])

    summary = None
    if trace:
        from bench.harness import trace as tr
        summary = tr.reduce(tr.find_xplane(logdir))
        if trace_dir is None:
            shutil.rmtree(logdir, ignore_errors=True)
        info.update(busy_s=summary.busy_s, window_s=summary.window_s)

    run = record.Run(
        config=cfg, traffic=mix, setup_s=setup_s, window=(t0, t1),
        events=events, num_edges=dep.num_edges,
        cost=cost.pd_iteration(dep.num_nodes, dep.num_edges,
                               dep.tenants[0].x.shape[2]),
        device_kind=info["kind"],
        trace=summary)
    metrics = {}
    for m in spec.metrics(workload, trace=trace):
        value = spec.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": len(events),
              "failed": sum(not e.ok for e in events),
              "metrics": metrics, "device": info}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = checks
    return result
