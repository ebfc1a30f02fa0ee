#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python3 bench/control.py --workload lattice512.serve_delta \
        --seconds 15 --control-seeds 11,12,13 --sound-seeds 21,22 \
        --fault drop_delta --fault-seeds 31,32,33

runs the cell's control (named in ``bench/limits/<cell>.json``: the
program in its lower-precision path) on each control seed, the cell as
it is on each sound seed, and the cell with a fault of
:mod:`bench.harness.faults` planted under its timed path on each fault
seed, one after another in this process, and prints one line per run
with every compared number.  The benchmark's own runs never run the
control or a fault.  Needs the accelerator, as ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--sound-seeds", type=_seeds, default=[])
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import contextlib

    from bench.harness.device import NoAccelerator
    from bench.harness.faults import FAULTS
    from bench.harness.runner import run_cell
    from bench.harness.system import import_program
    import_program(ROOT)        # a fault patches the program's modules
    if args.fault_seeds and args.fault not in FAULTS:
        ap.error(f"--fault-seeds needs --fault, one of {sorted(FAULTS)}")
    runs = ([(s, "control") for s in args.control_seeds]
            + [(s, "sound") for s in args.sound_seeds]
            + [(s, args.fault) for s in args.fault_seeds])
    try:
        for seed, kind in runs:
            t0 = time.perf_counter()
            planted = (FAULTS[kind]() if kind in FAULTS
                       else contextlib.nullcontext())
            with planted:
                res = run_cell(ROOT, args.workload, seed, args.seconds,
                               False, t_start=t0,
                               control_run=kind == "control")
            print("reading " + json.dumps({
                "workload": args.workload, "seed": seed,
                "run": kind, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "checks": {k: v["value"] for k, v in
                           res["checks"].items()}}), flush=True)
    except NoAccelerator as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
