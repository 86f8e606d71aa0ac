"""Triangulated grid: a 4-neighbour lattice plus one diagonal per cell,
interior degree 6 (the paper's aerodynamic meshes). Fixed by its sizes;
the seed is not used."""
from __future__ import annotations

import numpy as np


def make(seed: int, rows: int, cols: int):
    """(n, u, v): the undirected edge list, each edge once."""
    del seed
    vid = np.arange(rows * cols, dtype=np.int64).reshape(rows, cols)
    u = np.concatenate([vid[:, :-1].ravel(), vid[:-1, :].ravel(),
                        vid[:-1, :-1].ravel()])
    v = np.concatenate([vid[:, 1:].ravel(), vid[1:, :].ravel(),
                        vid[1:, 1:].ravel()])
    return rows * cols, u, v
