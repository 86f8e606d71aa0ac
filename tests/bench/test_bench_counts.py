"""The yardstick's arithmetic: the roofline floor, the peak table, the
float64 reference and the comparison that decides `correct`, and the metric
readers on hand-made runs."""
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import manifest, peaks, roofline  # noqa: E402
from bench.drive import Run  # noqa: E402
from bench.reference import (  # noqa: E402
    Reference, formula8_rounds, l1_error, topk_error)
from bench.trace import TraceSummary  # noqa: E402

V5E = peaks.peaks("TPU v5 lite")


def test_round_counts_by_hand():
    # n = 10, m = 40, B = 3: 4*40 index + 4*10 pointer + 2*4*10*3 x and y
    assert roofline.round_bytes(10, 40, 3) == 160 + 40 + 240
    assert roofline.round_flops(40, 3) == 240
    # mesh-1m at B = 1: 4 * 6,231,842 + 4 * 1,040,000 + 8 * 1,040,000
    assert roofline.round_bytes(1_040_000, 6_231_842, 1) == 37_407_368


def test_floor_takes_the_larger_bound():
    t, which = roofline.round_floor_s(10, 40, 3, V5E)
    assert which == "hbm" and t == pytest.approx(440 / 819e9)
    fast_mem = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    t, which = roofline.round_floor_s(10, 40, 3, fast_mem)
    assert which == "flops" and t == pytest.approx(240.0)


def test_peak_table():
    assert V5E == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16 * 2**30}
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _dense_pagerank(n, u, v, p, c):
    a = np.zeros((n, n))
    for x, y in zip(u, v):
        if x != y:
            a[x, y] = a[y, x] = 1.0
    for i in np.flatnonzero(a.sum(0) == 0):
        a[i, i] = 1.0
    P = a / a.sum(0)
    p = p / p.sum()
    return np.linalg.solve(np.eye(n) - c * P, (1 - c) * p)


def test_reference_matches_a_dense_solve():
    rng = np.random.default_rng(3)
    n = 40
    u, v = rng.integers(0, n, 90), rng.integers(0, n, 90)  # dups, loops,
    ref = Reference(n, u, v)                                 # isolated
    want = _dense_pagerank(n, u, v, np.ones(n), 0.85)
    assert l1_error(ref.global_pagerank(0.85), want) < 1e-10
    p = np.zeros(n)
    p[[3, 7]] = 1.0
    col = ref.ppr([(3, 7)], 0.85)[0]
    assert l1_error(col, _dense_pagerank(n, u, v, p, 0.85)) < 1e-10


def test_bfloat16_control_is_lower_precision():
    rng = np.random.default_rng(4)
    n = 500
    u, v = rng.integers(0, n, 3000), rng.integers(0, n, 3000)
    hi = Reference(n, u, v).global_pagerank(0.85)
    lo = Reference(n, u, v, dtype="bfloat16").global_pagerank(0.85)
    assert 1e-5 < l1_error(lo, hi) < 0.1


def test_topk_error():
    col = np.array([0.05, 0.4, 0.1, 0.3, 0.15])
    assert topk_error([1, 3], [0.4, 0.3], col, 2) == 0.0
    assert topk_error([1, 3], [0.41, 0.3], col, 2) == pytest.approx(0.01)
    # a wrong vertex with the right score is caught at that vertex
    assert topk_error([1, 4], [0.4, 0.3], col, 2) == pytest.approx(0.15)
    for bad in (([1, 1], [0.4, 0.3]), ([1], [0.4]), ([1, 3], [0.4, np.nan]),
                ([1, 9], [0.4, 0.3])):
        assert topk_error(*bad, col, 2) == float("inf")
    assert l1_error(np.ones(3), np.ones(4)) == float("inf")


def _reader(name):
    return manifest.reader(REPO, name)


def test_roofline_readers_by_hand():
    tr = TraceSummary(window_ns=(0.0, 2e9), busy_ns=1.5e9, n_devices=1,
                      modules={"jit_cpaa_fixed(3)": 1.0e9,
                               "jit__solve_topk_adaptive(7)": 0.5e9})
    solves = Run(seed=0, n=1000, m=8000, trace=tr, peak=V5E,
                 program="cpaa_fixed",
                 calls=[{"rounds": 10, "columns": 1}] * 2)
    floor1 = roofline.round_bytes(1000, 8000, 1) / 819e9
    assert _reader("spmm_roofline.global")(solves) == pytest.approx(
        100 * 20 * floor1 / 1.0)
    ticks = Run(seed=0, n=1000, m=8000, trace=tr, peak=V5E,
                program="solve_topk_adaptive",
                calls=[{"rounds": 4, "columns": 3},
                       {"rounds": 2, "columns": 1}])
    floor_serve = (4 * roofline.round_bytes(1000, 8000, 3)
                   + 2 * roofline.round_bytes(1000, 8000, 1)) / 819e9
    for name in ("spmm_roofline.serve", "spmm_roofline.bulk"):
        assert _reader(name)(ticks) == pytest.approx(100 * floor_serve / 0.5)
    for name in ("device_idle.global", "device_idle.serve",
                 "device_idle.bulk"):
        assert _reader(name)(ticks) == pytest.approx(25.0)
    # nothing to read: no trace, or no program in it
    assert _reader("spmm_roofline.global")(Run(seed=0)) is None
    ticks.trace = TraceSummary(window_ns=(0.0, 1.0), busy_ns=0.5, n_devices=1)
    assert _reader("spmm_roofline.serve")(ticks) is None


def test_counter_readers_by_hand():
    run = Run(seed=0, counters={
        "ticks": 4, "padded": 30, "bucket_cols": 512, "rounds_used": 60,
        "rounds_bound": 96, "stage.batch_form": 0.4,
        "stage.solve_dispatch": 0.1, "stage.materialize": 0.3,
        "stage.solve_device": 9.0})
    for name in ("host_tick_ms.serve", "host_tick_ms.bulk"):
        assert _reader(name)(run) == pytest.approx(200.0)
    assert _reader("padded_col_share.serve")(run) == pytest.approx(
        100 * 30 / 512)
    assert _reader("rounds_used_share.serve")(run) == pytest.approx(62.5)
    assert _reader("host_tick_ms.serve")(Run(seed=0)) is None


def test_end_to_end_readers_by_hand():
    run = Run(seed=0, setup_s=12.5, window_s=10.0, solves=4, answered=50,
              latencies=list(np.linspace(1.0, 2.0, 101)))
    assert _reader("setup_s")(run) == 12.5
    assert _reader("global_solve_s")(run) == 2.5
    assert _reader("ppr_qps")(run) == 5.0
    assert _reader("ppr_p95_s")(run) == pytest.approx(1.95)


def test_formula8_rounds_by_hand():
    # c = 0.85: b = (1 - sqrt(1 - 0.7225)) / 0.85 = 0.55672; ERR_M =
    # 2 b^(M+1) / (1 + b) is 1.010e-6 at M = 23 and 5.62e-7 at M = 24
    assert formula8_rounds(0.85, 1e-6) == 24
    # tol 1e-4: 1.094e-4 at M = 15, 6.09e-5 at M = 16
    assert formula8_rounds(0.85, 1e-4) == 16
    assert formula8_rounds(0.5, 0.5) == 1


def test_kronecker_generator_by_hand():
    kron = manifest.module(REPO, "graphs", "kronecker")
    n, u, v = kron.make(7, scale=6, edgefactor=4, A=0.57, B=0.19, C=0.19)
    assert n == 64 and u.size == v.size == 256
    assert 0 <= min(u.min(), v.min()) and max(u.max(), v.max()) < 64
    again = kron.make(7, scale=6, edgefactor=4, A=0.57, B=0.19, C=0.19)
    assert np.array_equal(u, again[1]) and np.array_equal(v, again[2])
    # A = 0 and D = 0: at every level the end bit is the start bit's
    # complement, so each vertex meets one partner only, never itself
    n, u, v = kron.make(3, scale=8, edgefactor=8, A=0.0, B=0.5, C=0.5)
    partner = {}
    for a, b in zip(u.tolist(), v.tolist()):
        assert a != b
        assert partner.setdefault(a, b) == b and partner.setdefault(b, a) == a
