"""With the timed path broken underneath, a run of the harness (its look
for a chip skipped) reports ``correct`` false, once for each fault a
cell can have (:mod:`bench.harness.faults`): a step that returns its
state unchanged, an answer altered where it is produced, and a served
request answered on the data as it stood before its delta."""
import jax
import pytest

from bench.harness.faults import FAULTS
from conftest import run


@pytest.fixture
def fresh_programs():
    """The program's jitted solves traced anew around the test, so a
    patch below them takes effect and leaves nothing behind."""
    jax.clear_caches()
    yield
    jax.clear_caches()


CASES = [("lattice512.offline", "unchanged"),
         ("lattice512.offline", "altered"),
         ("lattice512.serve_delta", "unchanged"),
         ("lattice512.serve_delta", "altered"),
         ("lattice512.serve_delta", "drop_delta")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(tiny_root, workload, fault, fresh_programs):
    with FAULTS[fault]():
        res = run(tiny_root, workload)
    assert not res["correct"], res["checks"]
