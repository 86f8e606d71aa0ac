"""Incremental edge-update path: delta computation, in-place device
patches, engine refresh parity, selective cache invalidation, no-op
detection, edgeless epochs, and the warm-started re-solve tick."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import cpaa, true_pagerank_dense
from repro.graph import generators
from repro.graph.ops import EdgeSlots, device_graph, patch_device_graph
from repro.graph.structure import Graph, edge_delta
from repro.serve import GraphRegistry, PageRankService, PPRQuery
from repro.serve.graph_registry import _undirected_keys


def mesh_non_edges(g, count, offset=13, start=0):
    """(i, i + offset) pairs that are NOT tri_mesh edges (mesh offsets are
    1, cols, cols+1; callers pass an offset that avoids all three)."""
    return [(start + i, start + i + offset) for i in range(count)]


def random_non_edges(g, count, seed=0):
    rng = np.random.default_rng(seed)
    have = set(zip(np.minimum(g.src, g.dst).tolist(),
                   np.maximum(g.src, g.dst).tolist()))
    out = []
    while len(out) < count:
        u, v = int(rng.integers(0, g.n)), int(rng.integers(0, g.n))
        e = (min(u, v), max(u, v))
        if u != v and e not in have:
            have.add(e)
            out.append(e)
    return out


def service(g, mode="incremental", engine="auto", **kw):
    reg = GraphRegistry(update_mode=mode, engine=engine)
    reg.register("g", g)
    defaults = dict(max_batch=8, cache_capacity=64, max_top_k=8)
    defaults.update(kw)
    return PageRankService(reg, **defaults)


class TestEdgeDelta:
    def test_effective_sets_and_touched(self):
        g = generators.tri_mesh(5, 7)
        keys = _undirected_keys(g)
        n = g.n
        present = keys[0]
        absent = 0 * n + 13
        d = edge_delta(n, keys, insert_keys=[present, absent],
                       delete_keys=[keys[1]])
        np.testing.assert_array_equal(d.inserted, [absent])
        np.testing.assert_array_equal(d.deleted, [keys[1]])
        assert not d.is_noop
        expect = {0, 13, int(keys[1] // n), int(keys[1] % n)}
        assert set(d.touched.tolist()) == expect

    def test_noop_batch(self):
        g = generators.tri_mesh(5, 7)
        keys = _undirected_keys(g)
        n = g.n
        # duplicate insert + absent delete + delete-then-reinsert: all no-op
        d = edge_delta(n, keys, insert_keys=[keys[0], keys[2]],
                       delete_keys=[keys[2], 0 * n + 13])
        assert d.is_noop
        assert d.touched.size == 0

    def test_empty_key_set(self):
        d = edge_delta(10, np.empty(0, np.int64), insert_keys=[13],
                       delete_keys=[27])
        np.testing.assert_array_equal(d.inserted, [13])
        assert d.deleted.size == 0


class TestDevicePatchRoundTrip:
    """Insert a batch then delete the same batch == original DeviceGraph
    bit-for-bit, through both patch strategies (index scatter for slivers,
    mirror re-upload for bigger batches)."""

    @pytest.mark.parametrize("batch_size", [1, 40])
    def test_bit_for_bit(self, batch_size):
        g = generators.tri_mesh(9, 11)
        es = EdgeSlots.from_graph(g, 1024)
        dg = es.to_device()
        orig = {k: np.asarray(getattr(dg, k)).copy()
                for k in ("src", "dst", "w", "inv_deg")}
        keys0 = es.ekeys.copy()
        ins = np.array([u * g.n + v
                        for u, v in mesh_non_edges(g, batch_size)], np.int64)
        d1 = edge_delta(g.n, es.ekeys, ins, ())
        assert d1.inserted.size == batch_size   # true non-edges
        patch_device_graph(dg, es.apply_delta(d1))
        d2 = edge_delta(g.n, es.ekeys, (), ins)
        patch_device_graph(dg, es.apply_delta(d2))
        for k, v in orig.items():
            np.testing.assert_array_equal(np.asarray(getattr(dg, k)), v,
                                          err_msg=k)
        np.testing.assert_array_equal(es.ekeys, keys0)

    def test_mirror_matches_device_graph_builder(self):
        g = generators.tri_mesh(9, 11)
        es = EdgeSlots.from_graph(g, 1024)
        dg = es.to_device()
        ref = device_graph(g, pad_edges_to=1024)
        for k in ("src", "dst", "w", "inv_deg"):
            np.testing.assert_array_equal(np.asarray(getattr(dg, k)),
                                          np.asarray(getattr(ref, k)),
                                          err_msg=k)

    def test_device_arrays_never_alias_the_mutable_mirror(self):
        """jax's CPU backend zero-copies aligned numpy arrays; the mirror
        mutates its buffers in place on every batch, so the device graph
        must always receive private copies (both at build and on the bulk
        re-upload patch path)."""
        g = generators.tri_mesh(9, 11)
        es = EdgeSlots.from_graph(g, 1024)
        dg = es.to_device()
        src0 = np.asarray(dg.src).copy()
        es.src[:] = -1
        np.testing.assert_array_equal(np.asarray(dg.src), src0)
        es.src[:len(g.src)] = g.src        # restore
        es.src[len(g.src):] = 0
        # upload path: a batch big enough to take the bulk re-upload
        ins = np.array([u * g.n + v
                        for u, v in mesh_non_edges(g, 40)], np.int64)
        p = es.apply_delta(edge_delta(g.n, es.ekeys, ins, ()))
        assert p.slots.size * 64 >= es.cap     # really the upload path
        patch_device_graph(dg, p)
        snap = {k: np.asarray(getattr(dg, k)).copy()
                for k in ("src", "dst", "w")}
        es.apply_delta(edge_delta(g.n, es.ekeys, (), ins))  # mutates mirror
        for k, v in snap.items():
            np.testing.assert_array_equal(np.asarray(getattr(dg, k)), v,
                                          err_msg=k)

    def test_overflow_returns_none_and_leaves_mirror_untouched(self):
        g = generators.tri_mesh(9, 11)
        es = EdgeSlots.from_graph(g, g.m)    # zero headroom
        keys0 = es.ekeys.copy()
        deg0 = es.deg.copy()
        d = edge_delta(g.n, es.ekeys,
                       [u * g.n + v for u, v in mesh_non_edges(g, 2)], ())
        assert es.apply_delta(d) is None
        np.testing.assert_array_equal(es.ekeys, keys0)
        np.testing.assert_array_equal(es.deg, deg0)


ENGINES = ["coo", "ell", "hub-tail", "block_ell", "fused", "sharded-1d"]


class TestIncrementalVsRebuildParity:
    """The delta path must land on the same solve as a from-scratch rebuild
    (L1 <= 1e-6), per engine, including across a bucket-boundary crossing
    (which exercises the rebuild fallback mid-stream)."""

    def _churn(self, svc, batches):
        for i, b in enumerate(batches):
            svc.update_graph("g", insert=b)
            if i % 2 == 1:
                svc.update_graph("g", delete=b)

    @pytest.mark.parametrize("engine", ENGINES)
    def test_parity_after_churn(self, engine):
        g = generators.tri_mesh(9, 11)
        batches = [mesh_non_edges(g, 3, offset=13, start=7 * i)
                   for i in range(4)]
        svc_inc = service(g, "incremental", engine)
        svc_reb = service(g, "rebuild", engine)
        self._churn(svc_inc, batches)
        self._churn(svc_reb, batches)
        rg_i = svc_inc.registry.get("g")
        rg_r = svc_reb.registry.get("g")
        assert svc_inc.stats["incremental_updates"] > 0
        np.testing.assert_array_equal(rg_i.keys, rg_r.keys)
        # solve parity through the live engines + against a fresh build
        p = np.zeros(g.n, np.float32)
        p[5] = 1.0
        pi_i = np.asarray(cpaa(rg_i.engine, tol=1e-8, p=jnp.asarray(p)).pi)
        pi_r = np.asarray(cpaa(rg_r.engine, tol=1e-8, p=jnp.asarray(p)).pi)
        g_fresh = Graph.from_undirected_edges(g.n, rg_i.keys // g.n,
                                              rg_i.keys % g.n)
        pi_f = np.asarray(cpaa(device_graph(g_fresh), tol=1e-8,
                               p=jnp.asarray(p)).pi)
        assert np.abs(pi_i - pi_f).sum() <= 1e-6
        assert np.abs(pi_r - pi_f).sum() <= 1e-6

    def test_bucket_boundary_crossing_falls_back_and_stays_correct(self):
        g2 = generators.tri_mesh(9, 11)
        svc2 = service(g2, "incremental", "coo", max_top_k=4)
        cap0 = svc2.registry.get("g").slots.cap
        # enough fresh edges that 2 slots each overflow the bucket headroom
        big = random_non_edges(g2, (cap0 - g2.m) // 2 + 8, seed=3)
        svc2.update_graph("g", insert=big)
        rg = svc2.registry.get("g")
        assert not rg.last_update_incremental      # fallback taken
        assert rg.slots.cap > cap0                 # bucket grew
        assert rg.epoch == 1
        # parity after the crossing
        keys = rg.keys
        g_fresh = Graph.from_undirected_edges(g2.n, keys // g2.n,
                                              keys % g2.n)
        p = np.zeros(g2.n, np.float32)
        p[3] = 1.0
        pi_a = np.asarray(cpaa(rg.engine, tol=1e-8, p=jnp.asarray(p)).pi)
        pi_b = np.asarray(cpaa(device_graph(g_fresh), tol=1e-8,
                               p=jnp.asarray(p)).pi)
        assert np.abs(pi_a - pi_b).sum() <= 1e-6
        # and the NEXT update is incremental again in the grown bucket
        svc2.update_graph("g", delete=big[:4])
        assert svc2.registry.get("g").last_update_incremental

    def test_block_ell_refresh_keeps_perm_for_local_delta(self):
        g = generators.tri_mesh(12, 12)
        svc = service(g, "incremental", "block_ell")
        rg = svc.registry.get("g")
        perm0 = np.asarray(rg.engine.perm).copy()
        svc.update_graph("g", insert=[(0, 20)])
        rg = svc.registry.get("g")
        assert rg.last_update_incremental
        np.testing.assert_array_equal(np.asarray(rg.engine.perm), perm0)

    def test_sharded_refresh_keeps_mesh(self):
        g = generators.tri_mesh(9, 11)
        svc = service(g, "incremental", "sharded-1d")
        rg = svc.registry.get("g")
        mesh0 = rg.engine.mesh
        svc.update_graph("g", insert=[(0, 20)])
        assert svc.registry.get("g").engine.mesh is mesh0


class TestNoopUpdates:
    def test_noop_skips_rebuild_epoch_and_cache_flush(self):
        g = generators.tri_mesh(9, 11)
        svc = service(g, "incremental", "coo")
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(50,)))
        svc.run_until_drained()
        assert len(svc.cache) == 1
        rg = svc.registry.get("g")
        engine0, dg0, epoch0 = rg.engine, rg.dg, rg.epoch
        u, v = int(g.src[0]), int(g.dst[0])
        ep = svc.update_graph("g", insert=[(u, v)], delete=[(0, 98)])
        rg = svc.registry.get("g")
        assert ep == epoch0 and rg.epoch == epoch0
        assert rg.engine is engine0 and rg.dg is dg0   # nothing rebuilt
        assert len(svc.cache) == 1                     # nothing flushed
        assert svc.stats["updates"] == 1               # still counted
        assert svc.stats["noop_updates"] == 1
        hit = svc.submit(PPRQuery(qid=1, graph="g", seeds=(50,)))
        assert hit is not None and hit.cached

    def test_noop_in_rebuild_mode_too(self):
        g = generators.tri_mesh(9, 11)
        svc = service(g, "rebuild", "coo")
        epoch0 = svc.registry.get("g").epoch
        svc.update_graph("g", delete=[(0, 98)])
        assert svc.registry.get("g").epoch == epoch0


class TestSeedCanonicalization:
    def test_duplicate_seeds_share_cache_and_solve(self):
        g = generators.tri_mesh(9, 11)
        svc = service(g)
        q = PPRQuery(qid=0, graph="g", seeds=(7, 7, 21, 7))
        assert q.seeds == (7, 21)          # canonical at construction
        svc.submit(q)
        first = svc.run_until_drained()[0]
        # a duplicated-seed twin hits the deduped entry...
        hit = svc.submit(PPRQuery(qid=1, graph="g", seeds=(21, 7, 21)))
        assert hit is not None and hit.cached
        np.testing.assert_array_equal(hit.scores, first.scores)
        # ...and the served scores are correct FOR THE DEDUPED seed set
        p = np.zeros(g.n)
        p[[7, 21]] = 0.5
        oracle = true_pagerank_dense(g, 0.85, p=p)
        r = svc.query("g", (7, 7, 21), tol=1e-8, top_k=5)
        np.testing.assert_allclose(r.scores, oracle[r.indices],
                                   rtol=1e-4, atol=1e-6)


class TestDeleteToEmpty:
    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("mode", ["incremental", "rebuild"])
    def test_delete_every_edge_then_reinsert(self, engine, mode):
        g = generators.tri_mesh(5, 7)
        svc = service(g, mode, engine, max_top_k=4)
        keys0 = _undirected_keys(g)
        edges = [(int(k // g.n), int(k % g.n)) for k in keys0]
        svc.update_graph("g", delete=edges)
        rg = svc.registry.get("g")
        assert rg.keys.size == 0
        # the edgeless epoch is well-defined: every vertex isolated (self
        # loop patch), P = I, so PPR mass stays on the seed
        r = svc.query("g", (3,), top_k=4)
        assert r.indices[0] == 3 and r.scores[0] == pytest.approx(1.0)
        assert np.all(np.isfinite(r.scores))
        # global solve on the edgeless graph is uniform
        pi = np.asarray(cpaa(rg.engine, tol=1e-6).pi)
        np.testing.assert_allclose(pi, 1.0 / g.n, atol=1e-6)
        # re-insert everything: back to the original graph
        svc.update_graph("g", insert=edges)
        rg = svc.registry.get("g")
        np.testing.assert_array_equal(rg.keys, keys0)
        p = np.zeros(g.n, np.float32)
        p[3] = 1.0
        pi_a = np.asarray(cpaa(rg.engine, tol=1e-8, p=jnp.asarray(p)).pi)
        pi_b = np.asarray(cpaa(device_graph(g), tol=1e-8,
                               p=jnp.asarray(p)).pi)
        assert np.abs(pi_a - pi_b).sum() <= 1e-6


class TestSelectiveInvalidation:
    def test_far_entries_survive_near_entries_drop(self):
        g = generators.tri_mesh(13, 17)
        svc = service(g, invalidation_radius=2, cache_capacity=64)
        far_seed, near_seed = 220, 1    # near vertex 0; 220 is rows away
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(near_seed,)))
        svc.submit(PPRQuery(qid=1, graph="g", seeds=(far_seed,)))
        svc.run_until_drained()
        ep = svc.update_graph("g", insert=[(0, 35)])
        assert svc.stats["cache_dropped"] == 1
        assert svc.stats["cache_retained"] == 1
        # retained entry answers at the NEW epoch without a solve
        solves = svc.stats["solves"]
        hit = svc.submit(PPRQuery(qid=2, graph="g", seeds=(far_seed,)))
        assert hit is not None and hit.cached and hit.epoch == ep
        assert svc.stats["solves"] == solves
        # dropped entry misses and re-solves
        assert svc.submit(PPRQuery(qid=3, graph="g",
                                   seeds=(near_seed,))) is None

    def test_blanket_default_unchanged(self):
        g = generators.tri_mesh(9, 11)
        svc = service(g)               # invalidation_radius=None
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(90,)))
        svc.run_until_drained()
        svc.update_graph("g", insert=[(0, 20)])
        assert len(svc.cache) == 0

    def test_retained_entry_accuracy_vs_fresh_solve(self):
        """The Grolmusz locality bet, measured: a retained far entry's
        scores stay within serving tolerance of a fresh solve on the
        updated graph."""
        g = generators.tri_mesh(13, 17)
        svc = service(g, invalidation_radius=2, cache_capacity=64)
        far = 212
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(far,), tol=1e-6))
        svc.run_until_drained()
        svc.update_graph("g", insert=[(0, 35)])
        key = ("g", 1, (far,), 0.85, 1e-6)
        idx, scores = svc.cache.get(key, count=False)
        g_new = svc.registry.get("g").host
        p = np.zeros(g_new.n)
        p[far] = 1.0
        oracle = true_pagerank_dense(g_new, 0.85, p=p)
        assert np.max(np.abs(scores - oracle[idx])) < 1e-4

    def test_index_consistency_after_selective(self):
        from itertools import chain
        g = generators.tri_mesh(9, 11)
        svc = service(g, invalidation_radius=1)
        for i, s in enumerate([(0,), (50,), (90,)]):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=s))
        svc.run_until_drained()
        svc.update_graph("g", insert=[(0, 20)])
        cache = svc.cache
        indexed = set(chain.from_iterable(cache._by_graph.values()))
        assert indexed == set(cache._d)
        assert cache.stats()["retained"] == cache.retained > 0


class TestRefreshTick:
    def test_near_boundary_entry_refreshes_toward_oracle(self):
        g = generators.tri_mesh(13, 17)
        svc = service(g, invalidation_radius=1, refresh_batch=4,
                      refresh_rounds=30, cache_capacity=64)
        near_boundary = 2              # 2 hops from vertex 0
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(near_boundary,)))
        svc.run_until_drained()
        ep = svc.update_graph("g", insert=[(0, 120)])
        assert len(svc._refresh) == 1
        assert svc.refresh_tick() == 1
        assert svc.stats["refreshes"] == 1
        key = ("g", ep, (near_boundary,), 0.85, 1e-4)
        idx, scores = svc.cache.get(key, count=False)
        g_new = svc.registry.get("g").host
        p = np.zeros(g_new.n)
        p[near_boundary] = 1.0
        oracle = true_pagerank_dense(g_new, 0.85, p=p)
        assert np.max(np.abs(scores - oracle[idx])) < 1e-3

    def test_refresh_never_degrades_a_retained_entry(self):
        """The cached warm start is top-k TRUNCATED: on graphs where the
        top-k holds little mass, a fixed short refine pass would re-cache
        an answer orders of magnitude WORSE than the retained one. The
        round count must scale with the truncation gap so the refreshed
        entry is at least as close to the new-graph oracle."""
        g = generators.caveman(12, 10, seed=0)   # spread-out PPR mass
        svc = service(g, invalidation_radius=1, refresh_batch=4,
                      refresh_rounds=8, cache_capacity=64)
        seed_v = 25                              # clique 2
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(seed_v,)))
        svc.run_until_drained()
        # far-away insert (cliques 8/9) retains + queues the entry
        ep = svc.update_graph("g", insert=[(85, 95)])
        key = ("g", ep, (seed_v,), 0.85, 1e-4)
        assert svc.cache.get(key, count=False) is not None
        idx0, s0 = svc.cache.get(key, count=False)
        g_new = svc.registry.get("g").host
        p = np.zeros(g_new.n)
        p[seed_v] = 1.0
        oracle = true_pagerank_dense(g_new, 0.85, p=p)
        before = np.max(np.abs(s0 - oracle[idx0]))
        if len(svc._refresh):
            assert svc.refresh_tick() >= 1
            idx1, s1 = svc.cache.get(key, count=False)
            after = np.max(np.abs(s1 - oracle[idx1]))
            assert after <= max(before, 1e-4) + 1e-6

    def test_superseded_epoch_is_skipped(self):
        g = generators.tri_mesh(13, 17)
        svc = service(g, invalidation_radius=1, refresh_batch=4,
                      cache_capacity=64)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(2,)))
        svc.run_until_drained()
        svc.update_graph("g", insert=[(0, 120)])
        assert len(svc._refresh) == 1
        # a second update lands ON the entry's seed: the entry is dropped
        # and the queued refresh (stale epoch) must be skipped
        svc.update_graph("g", insert=[(2, 121)])
        assert svc.refresh_tick() == 0
        assert svc.stats["refreshes"] == 0


class TestUpdateChurnService:
    """Property-style end-to-end: random churn through the service keeps
    (a) the key set equal to a replayed rebuild registry and (b) answers
    equal to fresh solves."""

    def test_random_churn_equivalence(self):
        g = generators.tri_mesh(9, 11)
        svc_i = service(g, "incremental", "coo", max_top_k=4)
        svc_r = service(g, "rebuild", "coo", max_top_k=4)
        rng = np.random.default_rng(0)
        live = set()
        for step in range(12):
            if live and rng.random() < 0.4:
                k = min(len(live), int(rng.integers(1, 4)))
                batch = [live.pop() for _ in range(k)]
                for svc in (svc_i, svc_r):
                    svc.update_graph("g", delete=batch)
            else:
                batch = []
                while len(batch) < 3:
                    u, v = int(rng.integers(0, g.n)), int(rng.integers(0, g.n))
                    if u != v:
                        batch.append((min(u, v), max(u, v)))
                live.update(batch)
                for svc in (svc_i, svc_r):
                    svc.update_graph("g", insert=batch)
            ki = svc_i.registry.get("g").keys
            kr = svc_r.registry.get("g").keys
            np.testing.assert_array_equal(ki, kr)
        # end-state answers agree with a dense oracle on the final graph
        g_end = svc_i.registry.get("g").host
        seeds = (5, 50)
        ri = svc_i.query("g", seeds, tol=1e-8, top_k=4)
        rr = svc_r.query("g", seeds, tol=1e-8, top_k=4)
        p = np.zeros(g_end.n)
        p[list(seeds)] = 0.5
        oracle = true_pagerank_dense(g_end, 0.85, p=p)
        for r in (ri, rr):
            np.testing.assert_allclose(r.scores, oracle[r.indices],
                                       rtol=1e-4, atol=1e-6)
