"""Graph 500 Kronecker generator (graph500.org specification, section 3,
"Generating the Edge List", after its sample implementation).

N = 2^scale vertices and M = edgefactor * N edges. Each edge picks one bit
of its start and end vertex per level of the recursion: the start bit is 1
with probability 1 - (A + B) = C + D; the end bit is 1 with probability
B / (A + B) after a start bit of 0, and D / (C + D) after a 1. Vertex
labels are then permuted at random, and so is the edge list. Duplicate
edges, self loops and isolated vertices stay in the list, as the
specification has them; the graph's consumer handles them.
"""
from __future__ import annotations

import numpy as np


def make(seed: int, scale: int, edgefactor: int, A: float, B: float,
         C: float):
    """(n, u, v): the undirected edge list, each edge once."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edgefactor * n
    ab = A + B
    # the end bit is 0 below this draw: A/(A+B) after a 0, C/(C+D) after a 1
    end_zero = np.array([A / ab, C / (1.0 - ab)], np.float32)
    u = np.zeros(m, np.int32)
    v = np.zeros(m, np.int32)
    for level in range(scale):
        u_bit = (rng.random(m, dtype=np.float32) > np.float32(ab)).view(
            np.uint8)
        v_bit = (rng.random(m, dtype=np.float32) > end_zero[u_bit]).view(
            np.uint8)
        u |= u_bit.astype(np.int32) << level
        v |= v_bit.astype(np.int32) << level
    label = rng.permutation(n)
    order = rng.permutation(m)
    return n, label[u][order], label[v][order]
