"""The least work one PageRank round does on a general graph.

Whatever the engine, a round y = P x over n vertices, m directed edges and
B live (non-pad) columns has to read one 32-bit index per directed edge and
one per vertex (the structure, in its most compact general form: a CSR
column index and row pointer), read x once and write y once in float32,
and do a multiply and an add per edge and column. Padding, per-edge
weights, gathered copies of x and repeated passes are all extra, so this
is a floor: a share of it over the measured time cannot pass 100% unless
an engine moves fewer bytes than any general format can, and then the
count needs a benchmark change, not a share over 100%.
"""
from __future__ import annotations


def round_bytes(n: int, m: int, b: int) -> int:
    return 4 * m + 4 * n + 2 * 4 * n * b


def round_flops(m: int, b: int) -> int:
    return 2 * m * b


def round_floor_s(n: int, m: int, b: int, peak: dict) -> tuple[float, str]:
    """(seconds, which bound) for one round at the chip's peaks."""
    t_mem = round_bytes(n, m, b) / peak["hbm_bytes_per_s"]
    t_flop = round_flops(m, b) / peak["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
