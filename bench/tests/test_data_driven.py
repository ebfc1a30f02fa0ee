"""The harness finds a configuration, a traffic mix and a metric by
name: new files and entries run with no edit to a file already there.
A run without an accelerator fails instead of using the CPU."""
import json
import os
import subprocess
import sys

from conftest import SBM_TINY, run

NEW_METRIC = '''"""Calls completed per second of the window."""


def read(run):
    return len(run.events) / run.window_s
'''


def test_new_files_are_found_by_name(tiny_root):
    bench = os.path.join(tiny_root, "bench")
    cfg = dict(SBM_TINY, cluster_sizes=[8, 8, 8], num_labeled=6,
               cluster_weights=[[2.0, 2.0], [-2.0, 2.0], [0.0, -2.0]])
    with open(os.path.join(bench, "configs", "sbm_three.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "offline_short.json"),
              "w") as f:
        json.dump({"loop": "offline", "iters_per_call": 50,
                   "warm_calls": 1, "check_calls": 1}, f)
    with open(os.path.join(bench, "metrics", "calls_per_s.py"), "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(bench, "limits", "sbm3.offline_short.json"),
              "w") as f:
        json.dump({"control": "program:bfloat16",
                   "numbers": {"w_err": {"limit": 1e-3},
                               "u_err": {"limit": 1e-3},
                               "res_err": {"limit": 1e-2}}}, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "sbm_three", "source": "test",
                           "file": "bench/configs/sbm_three.json",
                           "reduced": [], "why": "three clusters"})
    doc["workloads"].append({"name": "sbm3.offline_short",
                             "config": "sbm_three",
                             "traffic": "offline_short", "chips": 1,
                             "why": "a cell added by files alone"})
    doc["end_to_end"].append({"name": "calls_per_s", "unit": "calls/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["sbm3.offline_short"]})
    with open(path, "w") as f:
        json.dump(doc, f)

    res = run(tiny_root, "sbm3.offline_short", seconds=0.5)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"calls_per_s", "setup_s"}
    assert res["metrics"]["calls_per_s"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_run_without_accelerator_fails(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny_root, "bench", "run.py"),
         "--workload", "lattice512.offline", "--seed", "4294967311",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())
