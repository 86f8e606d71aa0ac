"""PageRank solvers (single device).

* cpaa          — the paper's Chebyshev Polynomial Approximation Algorithm
                  (Algorithm 1), via the three-term recurrence
                  T_{k+1}(P)p = 2 P T_k(P)p − T_{k−1}(P)p.
* cpaa_adaptive — residual-controlled CPAA: same recurrence, run in chunks
                  inside a `lax.while_loop` with an a-posteriori exit as
                  soon as the normalized L1 residual between accumulator
                  snapshots drops under tol (never past the Formula 8
                  a-priori round bound). Batched [n, B] solves carry a
                  per-column convergence mask, so converged columns feed
                  zeros to the SpMM and stay frozen.
* power         — the Power method baseline (SPI in the paper).
* forward_push  — truncated-geometric-series baseline (algebraic Forward
                  Push / IFP1 analogue): pi_M ∝ Σ_{k<=M} (cP)^k p.
* monte_carlo   — random-walk estimator (the MC family the paper cites).

All solvers are jit-compatible (jax.lax control flow), support single
vectors [n] or batched personalization [n, B] (the TPU adaptation: B columns
feed the MXU), and return *normalized* PageRank (sums to 1 per column).

Normalization contract: the DEFAULT personalization of every solver is
uniform with UNIT mass (p_i = 1/n). The final per-column normalization
absorbs any scaling of p, so `pi` is unaffected by it — but `keep_history`
accumulators, residuals and any intermediate mass readings are comparable
across solvers only because they all start from the same mass-1 default.
(The paper's Algorithm 1 seeds T_i = 1, i.e. mass n; divide by n to map its
intermediate quantities onto ours.)

The first argument of every solver is a DeviceGraph **or an Engine**
(`core.engine`): a DeviceGraph is wrapped in the COO segment-sum engine for
backwards compatibility, while a BlockEllEngine / FusedBlockEllEngine routes
every iteration through the Pallas block-ELL SpMM (and fused Chebyshev
update) instead. Engines own their internal layout (BFS permutation, block
padding); solvers convert once at entry/exit, so callers always see original
vertex ids.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.chebyshev import (ChebSchedule, default_chunk, make_schedule)
from repro.core.engine import CooEngine, as_engine
from repro.graph.ops import DeviceGraph  # noqa: F401  (re-exported API surface)

__all__ = ["PageRankResult", "cpaa", "cpaa_adaptive", "power", "forward_push",
           "monte_carlo", "cpaa_fixed", "cpaa_adaptive_fixed", "power_refine",
           "true_pagerank_dense", "degree_prior"]


@dataclass
class PageRankResult:
    pi: jax.Array            # [n] or [n, B], column-normalized
    iterations: int          # rounds actually run (max over columns)
    history: jax.Array | None = None  # [M, ...] per-round accumulators if kept
    # adaptive-solve telemetry (None on the fixed-round paths):
    rounds_bound: int | None = None        # a-priori Formula 8 round count
    column_rounds: np.ndarray | None = None  # [] or [B] rounds per column
    residual: np.ndarray | None = None     # [] or [B] last chunk L1 residual

    @property
    def rounds_saved(self) -> int | None:
        """Rounds the residual exit saved vs the a-priori bound."""
        if self.rounds_bound is None:
            return None
        return self.rounds_bound - self.iterations


def _normalize(acc: jax.Array) -> jax.Array:
    # tiny guard: an all-zero column (empty / fully-filtered seed set) comes
    # back as zeros instead of 0/0 NaNs that would poison result caches
    s = jnp.sum(acc, axis=0, keepdims=(acc.ndim > 1))
    tiny = jnp.asarray(jnp.finfo(acc.dtype).tiny, acc.dtype)
    return acc / jnp.where(jnp.abs(s) < tiny, tiny, s)


def _uniform_p(eng) -> jax.Array:
    """Uniform UNIT-mass personalization (see the normalization contract)."""
    return jnp.full((eng.n,), 1.0 / eng.n, eng.dtype)


def degree_prior(g) -> np.ndarray:
    """deg / 2m — the stationary distribution of P on an undirected graph.

    Because P = A D^{-1} with a symmetric A, x = deg/2m satisfies P x = x
    exactly, so personalized PageRank seeded AT the prior returns the prior
    for every damping factor: pi(c, p=deg/2m) = deg/2m in exact arithmetic
    (Grolmusz's degree-plus-bounded-correction form with zero correction).
    That makes it an analytic oracle at any scale — the scale tests compare
    solver output against it where `true_pagerank_dense` (O(n^3)) is
    unaffordable. Host-side float64 numpy; takes a `Graph`.
    """
    # jaxlint: disable=JL003 -- analytic oracle is host float64 by design
    deg = np.asarray(g.deg, np.float64)
    return deg / max(deg.sum(), 1.0)


@partial(jax.jit, static_argnames=("rounds", "keep_history", "unroll"))
def cpaa_fixed(dg, coeffs: jax.Array, p: jax.Array,
               rounds: int, keep_history: bool = False,
               unroll: bool = False):
    """CPAA with a fixed round count (jit-friendly core).

    dg:     DeviceGraph or Engine (see module docstring).
    coeffs: [rounds+1] with coeffs[0] already halved (= c0/2).
    p:      [n] or [n, B] personalization (need not be normalized; the final
            normalization in Algorithm 1 line 36 absorbs scaling).
    unroll: fully unroll the round loop (the dry-run cost prober compiles
            reduced-depth variants and needs the rounds visible in the HLO).
    """
    eng = as_engine(dg)
    t_prev = eng.to_internal(p)     # T_0(P) p
    acc = coeffs[0] * t_prev        # (c0/2) T_0 p
    t_cur = eng.apply(t_prev)       # T_1(P) p = P p
    acc = acc + coeffs[1] * t_cur

    def body(carry, ck):
        t_prev, t_cur, acc = carry
        y = eng.apply(t_cur)        # SpMV/SpMM: the round's only graph work
        t_next, acc = eng.cheb_round(y, t_prev, acc, ck)
        return (t_cur, t_next, acc), \
            (eng.from_internal(acc) if keep_history else 0.0)

    (_, _, acc), hist = jax.lax.scan(
        body, (t_prev, t_cur, acc), coeffs[2:],
        unroll=max(1, coeffs.shape[0] - 2) if unroll else 1)
    return _normalize(eng.from_internal(acc)), hist


def cpaa(dg, c: float = 0.85, tol: float = 1e-6,
         p: jax.Array | None = None, schedule: ChebSchedule | None = None,
         keep_history: bool = False) -> PageRankResult:
    """The paper's Algorithm 1. Rounds chosen from ERR_M < tol (Formula 8)."""
    eng = as_engine(dg)
    sched = schedule or make_schedule(c, tol)
    if p is None:
        p = _uniform_p(eng)
    coeffs = jnp.asarray(sched.coeffs, p.dtype)
    pi, hist = cpaa_fixed(eng, coeffs, p, rounds=sched.rounds,
                          keep_history=keep_history)
    return PageRankResult(pi=pi, iterations=sched.rounds,
                          history=hist if keep_history else None)


@partial(jax.jit, static_argnames=("max_rounds", "chunk"))
def cpaa_adaptive_fixed(dg, p: jax.Array, c, tol, max_rounds: int,
                        chunk: int = 4):
    """Residual-controlled CPAA core (jit-friendly; all engines).

    Runs the Chebyshev recurrence in chunks of `chunk` rounds inside a
    `lax.while_loop`; after each chunk the normalized accumulator is
    snapshotted and the per-column L1 residual against the previous snapshot
    decides which columns keep iterating. Converged columns freeze (their
    recurrence state stops updating) and feed ZEROS into the SpMM, so a
    batched tick stops spending edge work on them; the loop exits when every
    column has converged or the a-priori bound `max_rounds` is hit — the
    adaptive solve can never run MORE rounds than `cpaa_fixed` at the same
    operating point.

    Coefficients are generated in-loop from the closed form c_k = c0 beta^k
    (Proposition 1: one multiply per round), so no coefficient vector is
    materialized and the trace is round-count-independent.

    Engine contract this relies on (all engines honor it): the internal
    layout is a permutation of the caller's vertices plus ZERO-mass padding
    rows that stay zero through every round, so column sums and L1 norms
    computed on internal-layout arrays equal the external ones. For the
    sharded engines the internal arrays are global (sharding-constrained)
    jax arrays, so the `jnp.sum` reductions below lower to the cross-shard
    psum the residual needs.

    Returns (pi, rounds_used, column_rounds, residual):
      pi            [n] / [n, B] column-normalized PageRank.
      rounds_used   () int32 — rounds actually run (max over columns).
      column_rounds [] / [B] int32 — rounds until each column converged.
      residual      [] / [B] — last chunk's normalized L1 residual.
    """
    eng = as_engine(dg)
    t_prev = eng.to_internal(p)         # T_0(P) p
    dtype = t_prev.dtype
    c = jnp.asarray(c, dtype)
    tol = jnp.asarray(tol, dtype)
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    sq = jnp.sqrt(1.0 - c * c).astype(dtype)
    beta = ((1.0 - sq) / c).astype(dtype)
    c0 = (2.0 / sq).astype(dtype)

    cols = () if t_prev.ndim == 1 else (t_prev.shape[1],)

    def colnorm(a):
        s = jnp.sum(a, axis=0)          # cross-shard psum on sharded engines
        return a / jnp.where(jnp.abs(s) < tiny, tiny, s)

    def widen(m):                       # [B] / () mask -> broadcastable
        return m if t_prev.ndim == 1 else m[None, :]

    acc = (0.5 * c0) * t_prev           # (c0/2) T_0 p
    t_cur = eng.apply(t_prev)           # T_1(P) p = P p
    ck = c0 * beta                      # c_1
    acc = acc + ck * t_cur

    active = jnp.ones(cols, bool)
    col_rounds = jnp.ones(cols, jnp.int32)
    resid0 = jnp.full(cols, jnp.inf, dtype)
    state = (t_prev, t_cur, acc, colnorm(acc), ck, jnp.int32(1), active,
             col_rounds, resid0)

    def cond(st):
        _, _, _, _, _, k, active, _, _ = st
        return jnp.logical_and(k < max_rounds, jnp.any(active))

    def body(st):
        t_prev, t_cur, acc, snap, ck, k, active, col_rounds, _ = st
        mask = widen(active)
        zero = jnp.zeros((), dtype)
        for _ in range(chunk):          # unrolled: chunk is small + static
            run = k < max_rounds        # stay within the a-priori bound
            ck_next = ck * beta
            y = eng.apply(jnp.where(mask, t_cur, zero))
            t_next, acc_next = eng.cheb_round(
                y, jnp.where(mask, t_prev, zero), acc, ck_next)
            upd = jnp.logical_and(run, mask)
            t_prev = jnp.where(upd, t_cur, t_prev)
            t_cur = jnp.where(upd, t_next, t_cur)
            acc = jnp.where(upd, acc_next, acc)
            ck = jnp.where(run, ck_next, ck)
            k = k + run.astype(jnp.int32)
        with jax.named_scope("cheb.residual"):
            norm = colnorm(acc)
            resid = jnp.sum(jnp.abs(norm - snap), axis=0)
            col_rounds = jnp.where(active, k, col_rounds)
            active = jnp.logical_and(active, resid > tol)
        return (t_prev, t_cur, acc, norm, ck, k, active, col_rounds, resid)

    (_, _, acc, _, _, k, _, col_rounds, resid) = jax.lax.while_loop(
        cond, body, state)
    return _normalize(eng.from_internal(acc)), k, col_rounds, resid


def cpaa_adaptive(dg, c: float = 0.85, tol: float | None = None,
                  p: jax.Array | None = None,
                  schedule: ChebSchedule | None = None,
                  chunk: int | None = None) -> PageRankResult:
    """Algorithm 1 with runtime residual control (a-posteriori early exit).

    Same answer as `cpaa` to within tol, usually in fewer rounds: the
    Formula 8 bound assumes the worst spectrum, while real graphs converge
    at their spectral gap. The schedule's round count is kept as the hard
    cap, so `result.iterations <= result.rounds_bound` always holds; the
    telemetry fields on the returned PageRankResult record the savings.
    `tol` defaults to 1e-6 — or, when an explicit `schedule` is passed, to
    that schedule's err_bound, so the residual exit targets the same
    accuracy the schedule's cap was built for (the distributed builders'
    convention). `chunk` is the residual-check period (default:
    `default_chunk(c, tol)`, sized so an exit leaves a tail provably below
    tol).
    """
    eng = as_engine(dg)
    sched = schedule or make_schedule(c, tol if tol is not None else 1e-6)
    if tol is None:
        tol = float(sched.err_bound) if schedule is not None else 1e-6
    if p is None:
        p = _uniform_p(eng)
    if chunk is None:
        chunk = default_chunk(sched.c, tol)
    pi, k, col_rounds, resid = cpaa_adaptive_fixed(
        eng, p, sched.c, tol, max_rounds=sched.rounds, chunk=chunk)
    return PageRankResult(pi=pi, iterations=int(k),
                          rounds_bound=sched.rounds,
                          column_rounds=np.asarray(col_rounds),
                          residual=np.asarray(resid))


@partial(jax.jit, static_argnames=("rounds",))
def power_refine(dg, x0: jax.Array, p: jax.Array, c, rounds: int):
    """Warm-started refinement: `rounds` of x <- c P x + (1-c) p from x0.

    CPAA's Chebyshev series has no incremental form — each T_k(P)p depends
    on the whole recurrence history, so a cached result cannot be "resumed"
    through it. But the series converges to the same fixed point as the
    power/push recurrence, whose contraction factor c applies from ANY
    starting vector: a cached score vector that is already close (e.g. a
    retained serving-cache entry after a localized edge update) needs only
    the few rounds that c^rounds * ||x0 - pi|| < tol, not a cold solve.
    x0/p: [n] or [n, B] (x0 need not be exactly normalized — the final
    normalization absorbs drift). Returns column-normalized PageRank.
    """
    eng = as_engine(dg)
    x = eng.to_internal(x0)
    pp = eng.to_internal(p)
    pp = _normalize(pp)   # unit restart mass: the fixed point is the PPR
    c = jnp.asarray(c, x.dtype)

    def body(x, _):
        return (c * eng.apply(x) + (1.0 - c) * pp).astype(x.dtype), 0.0

    x, _ = jax.lax.scan(body, x, None, length=rounds)
    return _normalize(eng.from_internal(x))


@partial(jax.jit, static_argnames=("max_iter",))
def _power_fixed(dg, c: float, p: jax.Array, max_iter: int, tol: float):
    eng = as_engine(dg)
    x0 = eng.to_internal(p)
    tiny = jnp.asarray(jnp.finfo(x0.dtype).tiny, x0.dtype)

    def cond(carry):
        _, k, resid = carry
        return jnp.logical_and(k < max_iter, resid >= tol)

    def body(carry):
        x, k, _ = carry
        # cast back: the traced scalars c/tol would otherwise promote low-
        # precision personalizations (bf16) to f32 and break the carry types
        x_new = (c * eng.apply(x) + (1.0 - c) * x0).astype(x0.dtype)
        resid = jnp.max(jnp.abs(x_new - x)) / \
            jnp.maximum(jnp.max(jnp.abs(x_new)), tiny)
        return x_new, k + 1, resid.astype(x0.dtype)

    # residual carry in p's dtype (float64/bf16 personalizations included)
    inf = jnp.asarray(jnp.inf, x0.dtype)
    x, k, _ = jax.lax.while_loop(cond, body, (x0, jnp.int32(0), inf))
    return _normalize(eng.from_internal(x)), k


def power(dg, c: float = 0.85, tol: float = 1e-10,
          p: jax.Array | None = None, max_iter: int = 500) -> PageRankResult:
    """Power iteration x <- c P x + (1-c) p (the paper's SPI/MPI baseline)."""
    eng = as_engine(dg)
    if p is None:
        p = _uniform_p(eng)
    pi, k = _power_fixed(eng, c, p, max_iter, tol)
    return PageRankResult(pi=pi, iterations=int(k))


@partial(jax.jit, static_argnames=("rounds",))
def _fp_fixed(dg, c: float, p: jax.Array, rounds: int):
    eng = as_engine(dg)
    r0 = eng.to_internal(p)

    def body(carry, _):
        r, acc = carry
        r = c * eng.apply(r)       # residual mass pushed one hop
        return (r, acc + r), 0.0

    (_, acc), _ = jax.lax.scan(body, (r0, r0), None, length=rounds)
    return _normalize(eng.from_internal(acc))


def forward_push(dg, c: float = 0.85, rounds: int = 50,
                 p: jax.Array | None = None) -> PageRankResult:
    """Truncated geometric series Σ_{k<=M} (cP)^k p — the monomial-basis
    baseline CPAA is compared against (paper §1, §3)."""
    eng = as_engine(dg)
    if p is None:
        p = _uniform_p(eng)
    return PageRankResult(pi=_fp_fixed(eng, c, p, rounds), iterations=rounds)


@partial(jax.jit, static_argnames=("n", "walks_per_node", "max_len"))
def _mc_fixed(deg: jax.Array, row_start: jax.Array, dst_sorted: jax.Array,
              n: int, c: float, key: jax.Array, walks_per_node: int,
              max_len: int):
    """Random walks over a precomputed sorted-src CSR (DeviceGraph.csr()):
    for vertex u pick a uniform edge index in [row_start[u], row_start[u+1])."""
    walkers = jnp.tile(jnp.arange(n, dtype=jnp.int32), walks_per_node)
    alive = jnp.ones_like(walkers, jnp.bool_)
    counts = jnp.zeros((n,), jnp.float32)

    def body(k, carry):
        walkers, alive, counts, key = carry
        key, k1, k2 = jax.random.split(key, 3)
        d = deg[walkers]
        # dangling (degree-0, isolated) vertices have no edge range in the
        # CSR: a walk that reaches one terminates there instead of indexing
        # the NEXT vertex's edges through row_start (deg 0 made the offset
        # land on someone else's slot)
        stop = jnp.logical_or(jax.random.uniform(k1, walkers.shape) > c,
                              d == 0)
        terminating = jnp.logical_and(alive, stop)
        counts = counts + jax.ops.segment_sum(
            terminating.astype(jnp.float32), walkers, num_segments=n)
        alive = jnp.logical_and(alive, jnp.logical_not(stop))
        u = jax.random.uniform(k2, walkers.shape)
        pick = row_start[walkers] + (u * d).astype(jnp.int32)
        walkers = jnp.where(alive, dst_sorted[jnp.clip(pick, 0, dst_sorted.shape[0] - 1)], walkers)
        return walkers, alive, counts, key

    walkers, alive, counts, _ = jax.lax.fori_loop(
        0, max_len, body, (walkers, alive, counts, key))
    counts = counts + jax.ops.segment_sum(alive.astype(jnp.float32), walkers,
                                          num_segments=n)
    return counts / jnp.sum(counts)


def monte_carlo(dg, c: float = 0.85, walks_per_node: int = 16,
                max_len: int = 64, seed: int = 0) -> PageRankResult:
    """Terminating random walks; π_i ∝ #walks that stop at i (paper §1 [6])."""
    eng = as_engine(dg)
    if not isinstance(eng, CooEngine):
        raise TypeError("monte_carlo samples the COO edge list; pass a "
                        "DeviceGraph or CooEngine")
    deg, row_start, dst_sorted = eng.dg.csr()  # host-built once, cached
    if int(dst_sorted.shape[0]) == 0:
        # edgeless graph: every vertex is dangling, every walk stops at its
        # start (indexing the empty CSR under jit is undefined)
        return PageRankResult(pi=jnp.full((eng.dg.n,), 1.0 / eng.dg.n,
                                          jnp.float32), iterations=0)
    pi = _mc_fixed(deg, row_start, dst_sorted, eng.dg.n, c,
                   jax.random.PRNGKey(seed), walks_per_node, max_len)
    return PageRankResult(pi=pi, iterations=max_len)


def true_pagerank_dense(g, c: float = 0.85, p=None) -> jnp.ndarray:
    """O(n^3) direct solve (1-c)(I - cP)^{-1} p — test oracle for small graphs.

    p: optional [n] or [n, B] personalization (default uniform). Columns are
    normalized like the solvers' output (each sums to 1).
    """
    import numpy as np
    n = g.n
    # jaxlint: disable=JL003 -- O(n^3) float64 oracle, test ground truth only
    a = np.zeros((n, n), np.float64)
    a[g.dst, g.src] = 1.0
    deg = a.sum(axis=0)
    p_mat = a / np.maximum(deg, 1.0)[None, :]
    if p is None:
        p = np.ones(n) / n
    p = np.asarray(p, np.float64)  # jaxlint: disable=JL003 -- oracle precision
    pi = np.linalg.solve(np.eye(n) - c * p_mat, (1.0 - c) * p)
    return pi / pi.sum(axis=0, keepdims=p.ndim > 1)
