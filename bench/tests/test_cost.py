"""The bytes and operations model of one iteration, and the peaks."""
import pytest

from bench.harness import cost, peaks


def test_pd_iteration_hand_count():
    # a path 0-1-2-3: V = 4 nodes, E = 3 edges, n = 2 features
    # words read: w 8, u 6, P 16, b 8, endpoints 6, weights 3;
    # written: w 8, u 6  -> 61 words of 4 bytes
    # operations: 12 V n = 96, V (2 n^2 - n) = 24, 16 E n = 96
    c = cost.pd_iteration(4, 3, 2)
    assert c.bytes == 244
    assert c.flops == 216


def test_lattice_bytes_per_iteration():
    # the 512 x 512 lattice: per node w, b and the written w (2 words
    # each) and P (4); per edge u and the written u (2 each), the
    # endpoints (2) and the weight (1): about 25.1 MB an iteration
    V, E = 262144, 523264
    c = cost.pd_iteration(V, E, 2)
    assert c.bytes == 4 * (10 * V + 7 * E)
    assert abs(c.bytes / 1e6 - 25.1) < 0.05


def test_roofline_time_is_bound_by_memory():
    # about one operation a byte, far below the v5e's 240 a byte
    c = cost.pd_iteration(262144, 523264, 2)
    p = peaks.peaks("TPU v5 lite")
    assert c.flops / c.bytes < 1.0
    assert c.seconds(p) == pytest.approx(c.bytes / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
