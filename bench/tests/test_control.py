"""Each cell's control, run at a tiny size on the CPU, fails the
comparison (``correct`` false): the program in its bfloat16 storage
path."""
import pytest

from conftest import run

CELLS = ["lattice512.offline", "lattice512.serve_delta"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload, monkeypatch):
    # the bfloat16 storage path is the fused route's, which the program
    # takes by default only on a TPU
    monkeypatch.setenv("REPRO_FUSED", "1")
    res = run(tiny_root, workload, control_run=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    res = run(tiny_root, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1
