"""The least bytes and operations of one Algorithm-1 iteration.

Counted for the squared loss and total variation from the sizes alone:
each array an iteration must read or write once, unpadded, whatever
implements it (no lane padding, no incidence matrix, no halo):

    read   w (V n), u (E n), the prox matrix P (V n n) and vector b (V n),
           the edge endpoints (2 E int32) and weights (E)
    write  w (V n), u (E n)

all 4-byte words.  Operations count each add, multiply, divide,
absolute value, minimum and maximum as one:

    D^T u                       2 E n
    w - tau D^T u, + b          3 V n
    P (v + b)                   V (2 n^2 - n)
    z = 2 w_h - w, D z          2 V n + E n
    u + sigma D z, clip         4 E n
    relaxation of w             3 V n
    relaxation of u, clip       5 E n
    eq.-11 residual             4 V n + 4 E n

so 12 V n + V (2 n^2 - n) + 16 E n in all.
"""
from __future__ import annotations

import dataclasses

WORD = 4


@dataclasses.dataclass(frozen=True)
class IterationCost:
    bytes: int
    flops: int

    def seconds(self, peaks: dict) -> float:
        """The least time of one iteration: the larger of its bytes over
        the memory bandwidth and its operations over the peak rate."""
        return max(self.bytes / peaks["hbm_bytes_per_s"],
                   self.flops / peaks["flops_per_s"])


def pd_iteration(num_nodes: int, num_edges: int,
                 num_features: int) -> IterationCost:
    V, E, n = num_nodes, num_edges, num_features
    words = (V * n + E * n + V * n * n + V * n + 2 * E + E
             + V * n + E * n)
    flops = 12 * V * n + V * (2 * n * n - n) + 16 * E * n
    return IterationCost(bytes=WORD * words, flops=flops)
