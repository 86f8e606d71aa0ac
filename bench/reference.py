"""The plain reference: PageRank by power iteration in float64 on the host.

Semantics (the paper's, for an undirected graph): the edge list is made
simple (duplicates and self loops dropped, both directions kept), a vertex
with no edge keeps its mass through a self loop, P = A D^-1, and
x <- c P x + (1 - c) p is iterated to a fixed point, then normalized to
sum 1. Nothing here imports the program or reads what it built.

`dtype="bfloat16"` is the control: the same iteration with P's weights and
the iterate rounded to bfloat16 every round (sums in float32), the
precision step below the float32 the configurations state.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

REF_STEP = 1e-11      # stop when the L1 step falls below this, or ...


def formula8_rounds(c: float, tol: float) -> int:
    """The rounds M the paper's Formula 8 asks for at tolerance tol: the
    least M >= 1 with ERR_M = 2 b^(M+1) / (1 + b) < tol, where
    b = (1 - sqrt(1 - c^2)) / c."""
    b = (1.0 - np.sqrt(1.0 - c * c)) / c
    rounds = 1
    while 2.0 * b ** (rounds + 1) / (1.0 + b) >= tol:
        rounds += 1
    return rounds


def max_iter(c: float) -> int:
    """... after the rounds that take any start to within REF_STEP / 2 of
    the fixed point (each contracts the L1 error by c, from at most 2): a
    bfloat16 iterate never steps by less than its rounding."""
    return int(np.ceil(np.log(REF_STEP / 4) / np.log(c)))


class Reference:
    def __init__(self, n: int, u: np.ndarray, v: np.ndarray,
                 dtype: str = "float64"):
        import scipy.sparse as sp
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        keep = u != v
        lo = np.minimum(u[keep], v[keep])
        hi = np.maximum(u[keep], v[keep])
        keys = np.unique(lo * n + hi)
        lo, hi = keys // n, keys % n
        src = np.concatenate([lo, hi])
        dst = np.concatenate([hi, lo])
        iso = np.flatnonzero(np.bincount(src, minlength=n) == 0)
        src = np.concatenate([src, iso])
        dst = np.concatenate([dst, iso])
        deg = np.bincount(src, minlength=n).astype(np.float64)
        self.n = n
        self.m = int(src.size)
        self.dtype = dtype
        w = 1.0 / deg[src]
        if dtype == "bfloat16":
            import ml_dtypes
            self._round = lambda x: x.astype(ml_dtypes.bfloat16).astype(
                np.float32)
            w = self._round(w)
            work = np.float32
        elif dtype == "float64":
            self._round = lambda x: x
            work = np.float64
        else:
            raise ValueError(f"reference dtype {dtype!r}")
        self.P = sp.csr_matrix((w.astype(work), (dst, src)), shape=(n, n))
        self._work = work

    def solve(self, p: np.ndarray, c: float) -> np.ndarray:
        """One column: the PageRank of restart distribution p (any scale)."""
        p = np.asarray(p, np.float64)
        p = self._round((p / p.sum()).astype(self._work))
        x = p
        for _ in range(max_iter(c)):
            x_new = self._round((c * (self.P @ x) + (1.0 - c) * p)
                                .astype(self._work))
            step = float(np.abs(x_new - x).sum())
            x = x_new
            if step < REF_STEP:
                break
        x = x.astype(np.float64)
        return x / x.sum()

    def global_pagerank(self, c: float) -> np.ndarray:
        return self.solve(np.ones(self.n), c)

    def ppr(self, seed_sets, c: float, threads: int = 8) -> list[np.ndarray]:
        """One column per seed set (unit mass split over its seeds); one
        thread per column (scipy's sparse product releases the GIL)."""
        def col(seeds):
            p = np.zeros(self.n)
            p[list(seeds)] = 1.0
            return self.solve(p, c)
        with ThreadPoolExecutor(max_workers=max(1, min(threads,
                                                        len(seed_sets)))) as ex:
            return list(ex.map(col, seed_sets))


def l1_error(pi: np.ndarray, ref: np.ndarray) -> float:
    """L1 distance of a PageRank vector from the reference."""
    pi = np.asarray(pi, np.float64)
    if pi.shape != ref.shape or not np.all(np.isfinite(pi)):
        return float("inf")
    return float(np.abs(pi - ref).sum())


def topk_error(indices, scores, ref_col: np.ndarray, k: int) -> float:
    """Widest gap of a served top-k answer from the reference column: each
    served score against the reference at its vertex, and the i-th served
    score against the reference's i-th largest value. A malformed answer
    (wrong length, repeated or out-of-range vertex, non-finite score) reads
    infinity."""
    idx = np.asarray(indices)
    sc = np.asarray(scores, np.float64)
    n = ref_col.size
    want = min(k, n)
    if idx.shape != (want,) or sc.shape != (want,) or \
            not np.all(np.isfinite(sc)) or np.unique(idx).size != want or \
            idx.min() < 0 or idx.max() >= n:
        return float("inf")
    top = np.partition(ref_col, n - want)[n - want:]
    top = np.sort(top)[::-1]
    return max(float(np.max(np.abs(sc - ref_col[idx]))),
               float(np.max(np.abs(sc - top))))
