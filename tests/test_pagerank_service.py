"""Online PPR query service: batching correctness, cache, epochs, top-k."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import cpaa, true_pagerank_dense
from repro.graph import generators
from repro.graph.ops import device_graph
from repro.serve import GraphRegistry, PageRankService, PPRQuery
from repro.serve.graph_registry import _undirected_keys


def make_service(g, **kw):
    registry = GraphRegistry()
    registry.register("g", g)
    defaults = dict(max_batch=8, cache_capacity=64, max_top_k=8)
    defaults.update(kw)
    return PageRankService(registry, **defaults)


def reference_topk(g, seeds, c, tol, k):
    """Per-query cpaa (single column) + host top-k."""
    p = np.zeros(g.n, np.float32)
    p[list(seeds)] = 1.0
    pi = np.asarray(cpaa(device_graph(g), c=c, tol=tol, p=jnp.asarray(p)).pi)
    idx = np.argsort(-pi, kind="stable")[:k]
    return idx, pi[idx]


class TestMicroBatching:
    def test_batched_answers_match_per_query_solves(self):
        g = generators.tri_mesh(13, 17)
        svc = make_service(g, max_batch=8)
        rng = np.random.default_rng(0)
        queries = [PPRQuery(qid=i, graph="g",
                            seeds=tuple(int(s) for s in
                                        rng.choice(g.n, 2, replace=False)),
                            top_k=5)
                   for i in range(6)]
        for q in queries:
            svc.submit(q)
        results = svc.run_until_drained()
        assert svc.stats["solves"] == 1          # 6 queries, ONE batched call
        assert svc.stats["solved_queries"] == 6
        for q in queries:
            ref_idx, ref_scores = reference_topk(g, q.seeds, q.c, q.tol, q.top_k)
            r = results[q.qid]
            np.testing.assert_allclose(r.scores, ref_scores,
                                       rtol=1e-5, atol=1e-5)
            # compare as sets: near-ties may swap order between solves
            assert set(r.indices.tolist()) == set(ref_idx.tolist())

    def test_groups_split_by_operating_point(self):
        """Different (c, tol) queries cannot share a coefficient vector."""
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(3,), c=0.85))
        svc.submit(PPRQuery(qid=1, graph="g", seeds=(5,), c=0.5))
        svc.run_until_drained()
        assert svc.stats["solves"] == 2

    def test_batch_padding_buckets(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=8)
        for i in range(3):  # 3 live queries pad to the 4-bucket
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        svc.run_until_drained()
        assert svc.stats["padded_columns"] == 1


class TestCache:
    def test_cache_hit_skips_recomputation(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        q = PPRQuery(qid=0, graph="g", seeds=(7, 21), top_k=5)
        assert svc.submit(q) is None             # cold: queued
        first = svc.run_until_drained()[0]
        solves_before = svc.stats["solves"]

        hit = svc.submit(PPRQuery(qid=1, graph="g", seeds=(7, 21), top_k=5))
        assert hit is not None and hit.cached    # served at submit time
        assert svc.stats["solves"] == solves_before
        np.testing.assert_array_equal(hit.indices, first.indices)
        np.testing.assert_array_equal(hit.scores, first.scores)

    def test_seed_order_is_canonicalized(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(21, 7)))
        svc.run_until_drained()
        hit = svc.submit(PPRQuery(qid=1, graph="g", seeds=(7, 21)))
        assert hit is not None and hit.cached

    def test_lru_eviction(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, cache_capacity=2)
        for i in range(4):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        svc.run_until_drained()
        assert len(svc.cache) == 2
        assert svc.cache.evictions == 2
        # oldest entries are gone -> resolves again
        assert svc.submit(PPRQuery(qid=10, graph="g", seeds=(0,))) is None


class TestResultCacheIndex:
    """The per-graph key index behind O(entries-for-that-graph)
    invalidation: it must stay exactly in sync with the LRU dict through
    puts, updates, evictions and invalidations, and the hit/miss/eviction/
    invalidation counters must stay exact through the churn."""

    def _check_index(self, cache):
        from itertools import chain
        indexed = set(chain.from_iterable(cache._by_graph.values()))
        assert indexed == set(cache._d)
        for graph, keys in cache._by_graph.items():
            assert keys and all(k[0] == graph for k in keys)

    def test_counters_exact_through_churn(self):
        from repro.serve.result_cache import ResultCache
        cache = ResultCache(capacity=4)
        # 6 puts over 2 graphs -> 2 evictions (the 2 oldest "a" keys)
        for i in range(3):
            cache.put(("a", 0, (i,)), i)
        for i in range(3):
            cache.put(("b", 0, (i,)), i)
        self._check_index(cache)
        assert len(cache) == 4 and cache.evictions == 2
        assert cache.get(("a", 0, (0,))) is None        # evicted -> miss
        assert cache.get(("a", 0, (2,))) == 2           # survivor -> hit
        assert cache.get(("b", 0, (1,))) == 1
        assert (cache.hits, cache.misses) == (2, 1)
        # duplicate put must not double-index
        cache.put(("b", 0, (1,)), 99)
        self._check_index(cache)
        assert len(cache) == 4 and cache.get(("b", 0, (1,))) == 99
        # invalidation drops exactly graph-b entries, counts them, and
        # leaves graph-a untouched
        dropped = cache.invalidate_graph("b")
        assert dropped == 3 and cache.invalidations == 3
        self._check_index(cache)
        assert len(cache) == 1 and cache.get(("a", 0, (2,))) == 2
        # invalidating an absent graph is a counted no-op
        assert cache.invalidate_graph("nope") == 0
        assert cache.invalidations == 3
        assert cache.stats() == {"size": 1, "capacity": 4, "hits": 4,
                                 "misses": 1, "evictions": 2,
                                 "invalidations": 3, "retained": 0}

    def test_index_survives_eviction_of_a_graphs_last_key(self):
        from repro.serve.result_cache import ResultCache
        cache = ResultCache(capacity=1)
        cache.put(("a", 0, (1,)), 1)
        cache.put(("b", 0, (1,)), 2)    # evicts a's only key
        self._check_index(cache)
        assert "a" not in cache._by_graph
        assert cache.invalidate_graph("a") == 0

    def test_service_invalidation_uses_index(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        for i in range(5):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        svc.run_until_drained()
        assert len(svc.cache) == 5
        svc.update_graph("g", insert=[(0, 90)])
        assert len(svc.cache) == 0 and svc.cache.invalidations == 5
        assert svc.cache._by_graph == {}


class TestZeroColumnGuard:
    def test_zero_personalization_column_cannot_poison_the_cache(self):
        """An all-zero column reaching the batched solve (an empty or fully-
        filtered seed set) must come back as finite zeros — NOT NaNs that
        would be cached and served. Exercised through the service's own
        jitted solve paths (fixed and adaptive)."""
        import jax.numpy as jnp
        from repro.serve.pagerank_service import (_solve_topk,
                                                  _solve_topk_adaptive)
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        rg = svc.registry.get("g")
        sched, coeffs = svc.registry.schedule(0.85, 1e-4)
        p = np.zeros((g.n, 2), np.float32)
        p[7, 0] = 1.0                       # live query; column 1 all-zero
        idx, scores = _solve_topk(rg.engine, coeffs, jnp.asarray(p),
                                  rounds=sched.rounds, k=4)
        assert np.all(np.isfinite(np.asarray(scores)))
        np.testing.assert_array_equal(np.asarray(scores)[1], 0.0)
        plan = svc.registry.adaptive_schedule(0.85, 1e-4)
        idx_a, scores_a, used, _, _ = _solve_topk_adaptive(
            rg.engine, jnp.asarray(p), plan.c, plan.tol,
            max_rounds=plan.max_rounds, chunk=plan.chunk, k=4)
        assert np.all(np.isfinite(np.asarray(scores_a)))
        np.testing.assert_array_equal(np.asarray(scores_a)[1], 0.0)
        assert int(used) <= plan.max_rounds
        # the live column is unaffected by its dead neighbour
        ref_idx, ref_scores = reference_topk(g, (7,), 0.85, 1e-4, 4)
        np.testing.assert_allclose(np.asarray(scores)[0], ref_scores,
                                   rtol=1e-5, atol=1e-6)


class TestDynamicUpdates:
    def test_update_bumps_epoch_and_invalidates(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        q = PPRQuery(qid=0, graph="g", seeds=(5, 50), top_k=5)
        svc.submit(q)
        stale = svc.run_until_drained()[0]
        assert stale.epoch == 0

        # connect two far-apart vertices: PPR mass must move
        epoch = svc.update_graph("g", insert=[(5, 90)])
        assert epoch == 1
        assert svc.cache.invalidations == 1

        res = svc.submit(PPRQuery(qid=1, graph="g", seeds=(5, 50), top_k=5))
        assert res is None                       # stale result NOT served
        fresh = svc.run_until_drained()[1]
        assert fresh.epoch == 1 and not fresh.cached
        assert not np.allclose(fresh.scores, stale.scores, atol=1e-7)

        # the fresh answer matches a from-scratch solve on the updated graph
        g_new = svc.registry.get("g").host
        ref_idx, ref_scores = reference_topk(g_new, q.seeds, q.c, q.tol, 5)
        np.testing.assert_allclose(fresh.scores, ref_scores,
                                   rtol=1e-5, atol=1e-5)

    def test_insert_then_delete_roundtrips(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        keys0 = _undirected_keys(svc.registry.get("g").host)
        svc.update_graph("g", insert=[(0, 77)])
        keys1 = _undirected_keys(svc.registry.get("g").host)
        assert len(keys1) == len(keys0) + 1
        svc.update_graph("g", delete=[(77, 0)])  # orientation-insensitive
        keys2 = _undirected_keys(svc.registry.get("g").host)
        np.testing.assert_array_equal(keys2, keys0)
        assert svc.registry.get("g").epoch == 2

    def test_duplicate_insert_and_absent_delete_are_noops(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        keys0 = _undirected_keys(g)
        u, v = int(g.src[0]), int(g.dst[0])
        svc.update_graph("g", insert=[(u, v)], delete=[(0, 98)])
        np.testing.assert_array_equal(
            _undirected_keys(svc.registry.get("g").host), keys0)


class TestTopK:
    def test_topk_agrees_with_dense_oracle(self):
        g = generators.tri_mesh(8, 9)
        svc = make_service(g, max_top_k=8)
        seeds = (3, 40)
        res = svc.query("g", seeds, tol=1e-8, top_k=8)

        p = np.zeros(g.n)
        p[list(seeds)] = 0.5
        oracle = true_pagerank_dense(g, 0.85, p=p)
        oracle_rank = np.argsort(-oracle, kind="stable")[:8]
        assert set(res.indices.tolist()) == set(oracle_rank.tolist())
        np.testing.assert_allclose(res.scores, oracle[res.indices],
                                   rtol=1e-4, atol=1e-6)
        # scores come back ranked
        assert np.all(np.diff(res.scores) <= 1e-12)

    def test_topk_truncation_per_query(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_top_k=8)
        r3 = svc.query("g", (4,), top_k=3)
        r8 = svc.query("g", (4,), top_k=8)
        assert len(r3.indices) == 3 and len(r8.indices) == 8
        np.testing.assert_array_equal(r3.indices, r8.indices[:3])


class TestValidation:
    def test_rejects_bad_queries(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_top_k=8)
        with pytest.raises(ValueError):
            svc.submit(PPRQuery(qid=0, graph="g", seeds=()))
        with pytest.raises(ValueError):
            svc.submit(PPRQuery(qid=1, graph="g", seeds=(g.n,)))
        with pytest.raises(ValueError):
            svc.submit(PPRQuery(qid=2, graph="g", seeds=(0,), top_k=9))
        with pytest.raises(KeyError):
            svc.submit(PPRQuery(qid=3, graph="nope", seeds=(0,)))

    def test_registry_rejects_duplicates_and_bad_edges(self):
        registry = GraphRegistry()
        g = generators.tri_mesh(5, 5)
        registry.register("g", g)
        with pytest.raises(ValueError):
            registry.register("g", g)
        with pytest.raises(ValueError):
            registry.apply_updates("g", insert=[(0, g.n)])
        with pytest.raises(ValueError):
            registry.apply_updates("g", insert=[(3, 3)])


class TestRetraceGate:
    """Steady-state serving must not recompile: the RetraceGate
    (repro.analysis.retrace) watches the engine trace-time apply log."""

    def test_steady_state_ticks_have_zero_recompiles(self, retrace_gate):
        g = generators.tri_mesh(13, 17)
        svc = make_service(g, max_batch=8)
        # Warm up: first solo query compiles the bucket-1 solve.
        svc.query("g", seeds=(0, 1), top_k=5)
        svc.query("g", seeds=(2, 3), top_k=5)
        solves_before = svc.stats["solves"]
        with retrace_gate():
            for i in range(20):
                svc.query("g", seeds=(4 + i, 30 + i), top_k=5)
        # The gate must have watched real solves, not cache hits.
        assert svc.stats["solves"] == solves_before + 20

    def test_gate_trips_on_batch_bucket_change(self, retrace_gate):
        from repro.analysis.retrace import RetraceError

        g = generators.tri_mesh(13, 17)
        svc = make_service(g, max_batch=8)
        # jit caches and the trace log are process-wide: another test file
        # on this worker may already have compiled (and logged) this
        # graph's bucket-2 solve
        from repro.core.engine import reset_apply_counts
        jax.clear_caches()
        reset_apply_counts()
        svc.query("g", seeds=(0,), top_k=5)      # warm bucket 1 only
        with pytest.raises(RetraceError) as ei:
            with retrace_gate():
                # Two distinct-seed queries batch together -> bucket 2 ->
                # a fresh [n, 2] trace of the solve.
                svc.submit(PPRQuery(qid=100, graph="g", seeds=(1,), top_k=5))
                svc.submit(PPRQuery(qid=101, graph="g", seeds=(2,), top_k=5))
                svc.run_until_drained()
        msg = str(ei.value)
        assert "NEW signature" in msg        # shape drift, not pytree churn
        assert "warmup signatures" in msg    # the diff names both sides

    def test_gate_allowance_tolerates_expected_traces(self, retrace_gate):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=8)
        svc.query("g", seeds=(0,), top_k=5)
        with retrace_gate(allowed=4):
            svc.submit(PPRQuery(qid=200, graph="g", seeds=(1,), top_k=5))
            svc.submit(PPRQuery(qid=201, graph="g", seeds=(2,), top_k=5))
            svc.run_until_drained()
