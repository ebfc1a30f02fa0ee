"""Benchmark runner: one benchmark per paper table/figure + system reports.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table1 ... # subset
"""
from __future__ import annotations

import sys
import time

from benchmarks import fig2_convergence, fig3_pout, scaling, table1

ALL = {
    "table1": table1.run,
    "fig2": fig2_convergence.run,
    "fig3": fig3_pout.run,
    "scaling": scaling.run,
}


def main(argv=None):
    names = (argv if argv is not None else sys.argv[1:]) or list(ALL)
    results = {}
    t_start = time.time()
    for name in names:
        if name not in ALL:
            print(f"unknown benchmark {name!r}; available: {sorted(ALL)}")
            return 2
        print(f"\n########## {name} ##########")
        t0 = time.time()
        payload = ALL[name]()
        results[name] = payload.get("ok", True)
        print(f"[{name}] done in {time.time() - t0:.1f}s")

    print(f"\n========== benchmark summary ({time.time() - t_start:.0f}s) "
          "==========")
    for name, ok in results.items():
        print(f"  {name:10s} {'PASS' if ok else 'FAIL'}")
    return 0 if all(results.values()) else 1


if __name__ == "__main__":
    from benchmarks.common import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
