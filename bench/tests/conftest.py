"""Shared helpers for the benchmark's own tests (``pytest bench/tests``).

They run on the CPU at tiny sizes: a scratch copy of the benchmark
whose configurations and traffic are cut small, driven through the
harness with its look for a chip skipped.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# each configuration and traffic mix cut to a size the CPU runs in
# seconds; every other key is the committed one.  The served cells'
# limit is set for these sizes from their readings on the CPU: sound
# runs read resid_ratio up to about 1, the controls 60 or more.
TINY = {
    "configs/lattice_512.json": {"side": 12, "budget_iters": 2000},
    "traffic/offline.json": {"iters_per_call": 100},
}
# the paper's Section 5 experiment (two clusters of 150 nodes) as a
# configuration of the ``sbm`` family, cut to two clusters of 12: the
# reference and the data-driven test run on this second graph family
SBM_TINY = {
    "family": "sbm", "reference": "nlasso_squared_tv",
    "cluster_sizes": [12, 12], "p_in": 0.5, "p_out": 0.001,
    "edge_weight": 1.0, "features": 2, "samples_per_node": 5,
    "num_labeled": 8, "cluster_weights": [[2.0, 2.0], [-2.0, 2.0]],
    "label_noise": 0.0, "remeasure_noise": 0.1, "tenants": 1,
    "lam": 0.001, "loss": "squared", "regularizer": "tv",
    "backend": "pallas", "rho": 1.9, "metric_every": 50, "tol": 0.001,
    "budget_iters": 4000, "dtype": "float32",
}
TINY_LIMITS = {"resid_ratio": 5.0}


def make_root(dest: str) -> str:
    """A checkout at ``dest``: the benchmark's files, cut to tiny sizes,
    and the program's ``src``."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dest, "src"))
    for rel, over in TINY.items():
        path = os.path.join(dest, "bench", rel)
        with open(path) as f:
            doc = json.load(f)
        doc.update(over)
        with open(path, "w") as f:
            json.dump(doc, f)
    limits = os.path.join(dest, "bench", "limits")
    for name in os.listdir(limits):
        path = os.path.join(limits, name)
        with open(path) as f:
            doc = json.load(f)
        for key, limit in TINY_LIMITS.items():
            if key in doc["numbers"]:
                doc["numbers"][key]["limit"] = limit
        with open(path, "w") as f:
            json.dump(doc, f)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path / "checkout"))


def run(root: str, workload: str, seed: int = 20260, seconds: float = 1.0,
        **kw) -> dict:
    """One run of ``workload`` on the CPU (no look for a chip)."""
    from bench.harness.runner import run_cell
    return run_cell(root, workload, seed, seconds, False,
                    t_start=time.perf_counter(), require_chip=False,
                    log=lambda *a: None, **kw)
