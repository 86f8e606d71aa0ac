"""Adaptive (residual-controlled) CPAA: parity, round caps, masking, guards.

The contract under test (ISSUE 4 tentpole):
  * cpaa_adaptive matches the dense oracle to L1 <= tol for [n] and [n, B]
    personalizations, on every single-device engine (the sharded engines are
    covered by tests/test_sharded_engine.py, which CI also runs under 8
    simulated devices);
  * the adaptive solve NEVER runs more rounds than the a-priori Formula 8
    bound (the fixed-round cpaa cost at the same operating point);
  * batched solves converge per column: easy columns freeze early while
    hard columns keep iterating, and frozen columns stay exactly correct;
  * an all-zero personalization column comes back as zeros, not NaNs.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (chunk_tail_ratio, cpaa, cpaa_adaptive,
                        cpaa_adaptive_fixed, default_chunk, make_schedule,
                        true_pagerank_dense)
from repro.core.engine import (BlockEllEngine, CooEngine, EllEngine,
                               FusedBlockEllEngine, HubTailEngine)
from repro.graph import generators
from repro.graph.ops import device_graph

GRAPHS = {
    "mesh": lambda: generators.tri_mesh(9, 11),
    "powerlaw": lambda: generators.powerlaw_ba(120, 3, seed=2),
    "kmer": lambda: generators.kmer_chains(200, seed=4),
}

ENGINES = {
    "coo": lambda g: CooEngine(device_graph(g)),
    "block_ell": lambda g: BlockEllEngine.from_graph(g, block=32,
                                                     use_kernel=False),
    "fused": lambda g: FusedBlockEllEngine.from_graph(g, block=32,
                                                      use_kernel=False),
    "ell": lambda g: EllEngine.from_graph(g),
    # a hub bar of 6 leaves both halves non-empty on the graphs whose
    # degrees allow it
    "hub_tail": lambda g: HubTailEngine.from_graph(g, hub_min_deg=6),
}

TOL = 1e-6
# House tolerances (same rationale as tests/test_sharded_engine.py): CPAA's
# Formula 8 controls the unaccumulated mass FRACTION, not a strict L1 — on
# graphs with degenerate spectra the fixed-round L1 vs the dense oracle sits
# a small constant above tol, and float32 accumulation adds ~n ulps. So:
# solve tight (1e-8), assert L1 <= 1e-5 vs the oracle, and hold the
# adaptive<->fixed PARITY (and the early-exit soundness, where the residual
# control actually fired) to the strict bound.
SOLVE_TOL = 1e-8
L1_SLACK = 1e-5


def seed_batch(g, B=4, seed=3):
    rng = np.random.default_rng(seed)
    p = np.zeros((g.n, B), np.float32)
    for j in range(B):
        p[rng.choice(g.n, rng.integers(1, 4), replace=False), j] = 1.0
    return p


class TestAdaptiveParity:
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("ename", sorted(ENGINES))
    def test_vector_matches_oracle_within_tol(self, gname, ename):
        g = GRAPHS[gname]()
        eng = ENGINES[ename](g)
        res = cpaa_adaptive(eng, 0.85, SOLVE_TOL)
        truth = true_pagerank_dense(g, 0.85)
        pi = np.asarray(res.pi, np.float64)
        assert pi.shape == (g.n,)
        assert np.abs(pi - truth).sum() <= L1_SLACK
        assert res.iterations <= res.rounds_bound

    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("ename", sorted(ENGINES))
    def test_batched_matches_oracle_and_fixed(self, gname, ename):
        g = GRAPHS[gname]()
        eng = ENGINES[ename](g)
        p = seed_batch(g)
        res = cpaa_adaptive(eng, 0.85, SOLVE_TOL, p=jnp.asarray(p))
        assert res.pi.shape == p.shape
        oracle = np.asarray(true_pagerank_dense(g, 0.85, p=p))
        fixed = np.asarray(cpaa(eng, 0.85, SOLVE_TOL, p=jnp.asarray(p)).pi)
        pi = np.asarray(res.pi, np.float64)
        for j in range(p.shape[1]):
            assert np.abs(pi[:, j] - oracle[:, j]).sum() <= L1_SLACK
            assert np.abs(pi[:, j] - fixed[:, j]).sum() <= L1_SLACK
        assert res.column_rounds.shape == (p.shape[1],)
        assert res.iterations == res.column_rounds.max()

    def test_engines_agree_with_each_other(self):
        g = GRAPHS["mesh"]()
        p = jnp.asarray(seed_batch(g))
        pis = [np.asarray(cpaa_adaptive(make(g), 0.85, SOLVE_TOL, p=p).pi)
               for make in ENGINES.values()]
        for other in pis[1:]:
            np.testing.assert_allclose(pis[0], other, rtol=1e-5, atol=1e-7)


class TestAprioriCap:
    @pytest.mark.parametrize("c,tol", [(0.5, 1e-8), (0.85, 1e-4),
                                       (0.85, 1e-8), (0.95, 1e-6)])
    def test_never_exceeds_the_formula8_bound(self, c, tol):
        g = GRAPHS["mesh"]()
        dg = device_graph(g)
        sched = make_schedule(c, tol)
        for p in (None, jnp.asarray(seed_batch(g))):
            res = cpaa_adaptive(dg, c, tol, p=p)
            assert res.rounds_bound == sched.rounds
            assert res.iterations <= sched.rounds
            assert int(np.max(res.column_rounds)) <= sched.rounds

    def test_broad_personalization_exits_early(self):
        """The Grolmusz case: the degree prior is near-stationary for
        undirected graphs, so the residual exit fires well under the bound
        (this is the measured win the adaptive_compare bench tracks)."""
        g = generators.caveman(12, 16, seed=0)
        dg = device_graph(g)
        deg = np.maximum(np.asarray(g.deg, np.float64), 1.0)
        pdeg = jnp.asarray(deg / deg.sum(), jnp.float32)
        res = cpaa_adaptive(dg, 0.85, 1e-3, p=pdeg)
        assert res.iterations < res.rounds_bound
        truth = true_pagerank_dense(g, 0.85, p=np.asarray(pdeg))
        assert np.abs(np.asarray(res.pi, np.float64) - truth).sum() <= 1e-3


class TestPerColumnMasking:
    def test_mixed_batch_converges_per_column(self):
        """A batch mixing an easy (uniform) and hard (single-seed) column:
        the easy column freezes earlier, the hard one runs to its own exit,
        and BOTH stay correct — freezing must not corrupt frozen columns.
        The two contracts split by how each column finished: a column that
        EXITED EARLY did so because the residual justified tol; a column
        that rode the a-priori cap must match the fixed-round solve."""
        tol = 1e-5
        g = generators.caveman(12, 16, seed=0)
        dg = device_graph(g)
        n = g.n
        p = np.zeros((n, 3), np.float32)
        p[:, 0] = 1.0 / n
        p[3, 1] = 1.0
        p[[5, n - 1], 2] = 0.5
        res = cpaa_adaptive(dg, 0.85, tol, p=jnp.asarray(p))
        assert res.column_rounds[0] < res.column_rounds[1]
        oracle = np.asarray(true_pagerank_dense(g, 0.85, p=p))
        fixed = np.asarray(cpaa(dg, 0.85, tol, p=jnp.asarray(p)).pi,
                           np.float64)
        pi = np.asarray(res.pi, np.float64)
        for j in range(3):
            if res.column_rounds[j] < res.rounds_bound:   # early exit
                assert np.abs(pi[:, j] - oracle[:, j]).sum() <= tol
            # cap or not, never worse than the fixed-round answer
            assert np.abs(pi[:, j] - fixed[:, j]).sum() <= tol

    def test_batched_equals_columnwise_singles(self):
        g = GRAPHS["powerlaw"]()
        dg = device_graph(g)
        p = seed_batch(g, B=5, seed=11)
        batched = np.asarray(cpaa_adaptive(dg, 0.85, TOL,
                                           p=jnp.asarray(p)).pi)
        for j in range(p.shape[1]):
            single = np.asarray(cpaa_adaptive(dg, 0.85, TOL,
                                              p=jnp.asarray(p[:, j])).pi)
            np.testing.assert_allclose(batched[:, j], single,
                                       rtol=1e-5, atol=1e-8)


class TestZeroColumnGuard:
    def test_zero_column_yields_zeros_not_nans(self):
        g = GRAPHS["mesh"]()
        dg = device_graph(g)
        p = seed_batch(g, B=4)
        p[:, 2] = 0.0   # empty / fully-filtered seed set
        for solver in (lambda: cpaa(dg, 0.85, TOL, p=jnp.asarray(p)),
                       lambda: cpaa_adaptive(dg, 0.85, TOL,
                                             p=jnp.asarray(p))):
            pi = np.asarray(solver().pi)
            assert np.all(np.isfinite(pi))
            np.testing.assert_array_equal(pi[:, 2], 0.0)
            oracle = np.asarray(true_pagerank_dense(g, 0.85, p=p[:, :2]))
            np.testing.assert_allclose(pi[:, :2], oracle, rtol=1e-4,
                                       atol=1e-7)

    def test_all_zero_vector(self):
        g = GRAPHS["mesh"]()
        dg = device_graph(g)
        pi = np.asarray(cpaa(dg, 0.85, TOL,
                             p=jnp.zeros((g.n,), jnp.float32)).pi)
        assert np.all(np.isfinite(pi)) and np.all(pi == 0.0)


class TestChunkSizing:
    def test_default_chunk_bounds(self):
        for c in (0.5, 0.85, 0.95, 0.99):
            r = default_chunk(c)
            assert 2 <= r <= 8
            # the sizing invariant: an exit at chunk residual <= tol leaves
            # a geometric tail provably below safety * tol
            if chunk_tail_ratio(c, r) > 0.5:
                assert r == 8   # clamp hit (very high damping factors)

    def test_chunk_grows_with_damping(self):
        assert default_chunk(0.95) >= default_chunk(0.85) >= default_chunk(0.5)

    def test_tol_caps_chunk_below_the_round_bound(self):
        # loose tolerance -> tiny a-priori bound -> chunk must shrink so at
        # least one residual check happens BEFORE the cap (strictly below
        # the bound, down to a 1-round chunk at very loose tolerances)
        for c, tol in ((0.85, 1e-2), (0.5, 1e-1)):
            bound = make_schedule(c, tol).rounds
            assert default_chunk(c, tol) <= max(1, bound - 1)
        assert default_chunk(0.5, tol=1e-1) == 1

    def test_schedule_without_tol_targets_the_schedules_err_bound(self):
        # an explicit schedule + default tol must not chase a tighter
        # residual than the schedule's cap was built for (which would ride
        # the cap on every solve and silently disable adaptivity)
        g = generators.caveman(12, 16, seed=0)
        dg = device_graph(g)
        deg = np.maximum(np.asarray(g.deg, np.float64), 1.0)
        pdeg = jnp.asarray(deg / deg.sum(), jnp.float32)
        sched = make_schedule(0.85, 1e-3)
        res = cpaa_adaptive(dg, schedule=sched, p=pdeg)
        assert res.rounds_bound == sched.rounds
        assert res.iterations < sched.rounds   # the broad prior exits early

    def test_explicit_chunk_respected(self):
        g = GRAPHS["mesh"]()
        dg = device_graph(g)
        truth = true_pagerank_dense(g, 0.85)
        for chunk in (2, 5):
            res = cpaa_adaptive(dg, 0.85, SOLVE_TOL, chunk=chunk)
            assert np.abs(np.asarray(res.pi, np.float64) - truth).sum() \
                <= L1_SLACK


class TestAdaptiveService:
    def _service(self, g, **kw):
        from repro.serve import GraphRegistry, PageRankService
        reg = GraphRegistry()
        reg.register("g", g)
        return PageRankService(reg, max_batch=8, cache_capacity=64,
                               max_top_k=8, adaptive=True, **kw)

    def test_adaptive_tick_matches_oracle(self):
        from repro.serve import PPRQuery
        g = generators.tri_mesh(8, 9)
        svc = self._service(g)
        seeds = (3, 40)
        res = svc.query("g", seeds, tol=1e-8, top_k=8)
        p = np.zeros(g.n)
        p[list(seeds)] = 0.5
        oracle = true_pagerank_dense(g, 0.85, p=p)
        assert set(res.indices.tolist()) == \
            set(np.argsort(-oracle, kind="stable")[:8].tolist())
        np.testing.assert_allclose(res.scores, oracle[res.indices],
                                   rtol=1e-4, atol=1e-6)
        assert 0 < svc.stats["rounds_used"] <= svc.stats["rounds_bound"]

    def test_registry_adaptive_schedule_cached_and_capped(self):
        from repro.serve import GraphRegistry
        reg = GraphRegistry()
        plan = reg.adaptive_schedule(0.85, 1e-4)
        assert plan is reg.adaptive_schedule(0.85, 1e-4)   # cache hit
        sched, _ = reg.schedule(0.85, 1e-4)
        assert plan.max_rounds == sched.rounds
        assert reg.adaptive_schedule(0.85, 1e-4, chunk=2).chunk == 2

    def test_per_tick_rounds_drop_on_broad_queries(self):
        """A broad (near-degree-prior) seed set converges before the bound:
        the tick's round telemetry must show the savings."""
        from repro.serve import PPRQuery
        g = generators.caveman(12, 16, seed=0)
        svc = self._service(g)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=tuple(range(g.n)),
                            tol=1e-3, top_k=4))
        svc.run_until_drained()
        assert svc.stats["rounds_used"] < svc.stats["rounds_bound"]
