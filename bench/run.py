#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload lattice512.offline --seed 7 \
        --seconds 10 --trace 0

from the root of a checkout.  The cell, its configuration, traffic mix,
metrics and limits are found by name from ``BENCHMARK.json``.  Set-up
(imports, the deployment built from ``--seed``, planning, compiles,
warm-up) is reported as ``setup_s``; then the window measures for
``--seconds`` and closes at the first completion after that; then the
answers are checked against the plain reference.  ``--trace 1`` traces
the window with the profiler and reports the per-layer metrics instead
of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit); the last lines of standard error repeat
the checks.  Without an accelerator, or with fewer chips than the cell
asks for, the run exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "directory in the checkout, deleted once read)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.harness.device import NoAccelerator
    from bench.harness.runner import run_cell
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START,
                          trace_dir=args.trace_dir)
    except NoAccelerator as exc:
        print(f"bench: {exc}; nothing was run", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
