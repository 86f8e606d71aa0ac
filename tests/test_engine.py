"""Engine parity suite: every SpMM engine must produce the same PageRank.

Covers: each engine vs the dense direct-solve oracle on mesh / powerlaw /
kmer generators, batched [n, B] and single [n] personalizations, the
BlockEll perm/padding round-trip, fused-vs-unfused round equivalence, the
selection heuristic, and the serving registry's per-epoch engine cache.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (cpaa, cpaa_fixed, forward_push, make_schedule, power,
                        true_pagerank_dense)
from repro.core.engine import (BlockEllEngine, CooEngine, EllEngine,
                               FusedBlockEllEngine, HubTailEngine, as_engine,
                               heuristic_mode, select_engine)
from repro.graph import generators
from repro.graph.ops import device_graph, spmv

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


class TestEngineParity:
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("ename", sorted(ENGINES))
    def test_single_vector_matches_oracle(self, gname, ename):
        g = GRAPHS[gname]()
        eng = ENGINES[ename](g)
        truth = true_pagerank_dense(g, 0.85)
        pi = np.asarray(cpaa(eng, 0.85, 1e-8).pi, np.float64)
        assert pi.shape == (g.n,)
        assert np.max(np.abs(pi - truth) / truth) < 5e-5

    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("ename", sorted(ENGINES))
    def test_batched_matches_oracle(self, gname, ename):
        g = GRAPHS[gname]()
        eng = ENGINES[ename](g)
        rng = np.random.default_rng(3)
        B = 4
        p = np.zeros((g.n, B), np.float32)
        for j in range(B):
            seeds = rng.choice(g.n, rng.integers(1, 4), replace=False)
            p[seeds, j] = 1.0
        pi = np.asarray(cpaa(eng, 0.85, 1e-8, p=jnp.asarray(p)).pi)
        assert pi.shape == (g.n, B)
        oracle = np.asarray(true_pagerank_dense(g, 0.85, p=p))
        np.testing.assert_allclose(pi, oracle, rtol=1e-4, atol=1e-7)

    def test_engines_agree_with_each_other(self):
        g = GRAPHS["mesh"]()
        p = jnp.asarray(np.random.default_rng(0).random(g.n), jnp.float32)
        pis = [np.asarray(cpaa(make(g), 0.85, 1e-8, p=p).pi)
               for make in ENGINES.values()]
        for other in pis[1:]:
            np.testing.assert_allclose(pis[0], other, rtol=1e-5, atol=1e-7)

    def test_pallas_kernel_path_through_engine(self):
        """The interpret-mode Pallas kernels, driven through the engine, match
        the COO solve (the TPU path minus the compiler)."""
        g = generators.tri_mesh(8, 9)
        eng = FusedBlockEllEngine.from_graph(g, block=16, use_kernel=True,
                                             interpret=True)
        sched = make_schedule(0.85, rounds=8)
        coeffs = jnp.asarray(sched.coeffs, jnp.float32)
        p = jnp.ones((g.n,), jnp.float32)
        pi_k, _ = cpaa_fixed(eng, coeffs, p, rounds=sched.rounds)
        pi_c, _ = cpaa_fixed(device_graph(g), coeffs, p, rounds=sched.rounds)
        np.testing.assert_allclose(np.asarray(pi_k), np.asarray(pi_c),
                                   rtol=2e-4, atol=1e-6)


class TestBlockEllRoundTrip:
    def test_to_from_internal_is_identity(self):
        g = generators.powerlaw_ba(150, 3, seed=1)
        eng = BlockEllEngine.from_graph(g, block=32)
        assert eng.n_pad >= g.n and eng.n_pad % eng.block == 0
        for shape in [(g.n,), (g.n, 5)]:
            x = jnp.asarray(np.random.default_rng(0).random(shape), jnp.float32)
            xi = eng.to_internal(x)
            assert xi.shape[0] == eng.n_pad
            np.testing.assert_array_equal(np.asarray(eng.from_internal(xi)),
                                          np.asarray(x))

    def test_apply_returns_original_ids(self):
        """engine.apply in internal layout == COO spmv in original ids."""
        g = generators.tri_mesh(11, 12)
        eng = BlockEllEngine.from_graph(g, block=32, use_kernel=False)
        dg = device_graph(g)
        x = jax.random.normal(jax.random.PRNGKey(2), (g.n,), jnp.float32)
        y = eng.from_internal(eng.apply(eng.to_internal(x)))
        np.testing.assert_allclose(np.asarray(y), np.asarray(spmv(dg, x)),
                                   rtol=2e-4, atol=1e-5)

    def test_padding_rows_stay_zero(self):
        g = generators.kmer_chains(150, seed=2)  # n not a multiple of block
        eng = BlockEllEngine.from_graph(g, block=64, use_kernel=False)
        assert eng.n_pad > g.n
        x = eng.to_internal(jnp.ones((g.n,), jnp.float32))
        y = eng.apply(x)
        assert float(jnp.max(jnp.abs(y[g.n:]))) == 0.0

    def test_slot_padding_keeps_results(self):
        g = generators.tri_mesh(9, 11)
        a = BlockEllEngine.from_graph(g, block=32, use_kernel=False)
        b = BlockEllEngine.from_graph(g, block=32, use_kernel=False,
                                      pad_slots=True)
        assert b.block_cols.shape[1] >= a.block_cols.shape[1]
        assert b.block_cols.shape[1] % BlockEllEngine.SLOT_QUANTUM == 0
        x = jnp.asarray(np.random.default_rng(1).random(g.n), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(b.from_internal(b.apply(b.to_internal(x)))),
            np.asarray(a.from_internal(a.apply(a.to_internal(x)))),
            rtol=1e-6, atol=1e-7)


class TestFusedRound:
    def test_fused_round_equals_unfused(self):
        g = generators.tri_mesh(9, 11)
        unfused = BlockEllEngine.from_graph(g, block=32, use_kernel=False)
        fused = FusedBlockEllEngine.from_graph(g, block=32, use_kernel=False)
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        y, t, acc = (jax.random.normal(k, (unfused.n_pad, 4), jnp.float32)
                     for k in ks)
        tu, au = unfused.cheb_round(y, t, acc, 0.5567)
        tf, af = fused.cheb_round(y, t, acc, 0.5567)
        np.testing.assert_allclose(np.asarray(tf), np.asarray(tu), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(af), np.asarray(au),
                                   rtol=1e-5, atol=1e-6)

    def test_fused_solve_equals_unfused_solve(self):
        g = generators.powerlaw_ba(100, 3, seed=4)
        sched = make_schedule(0.85, rounds=10)
        coeffs = jnp.asarray(sched.coeffs, jnp.float32)
        p = jnp.ones((g.n,), jnp.float32)
        pi_u, _ = cpaa_fixed(BlockEllEngine.from_graph(g, block=32,
                                                       use_kernel=False),
                             coeffs, p, rounds=sched.rounds)
        pi_f, _ = cpaa_fixed(FusedBlockEllEngine.from_graph(g, block=32,
                                                            use_kernel=False),
                             coeffs, p, rounds=sched.rounds)
        np.testing.assert_allclose(np.asarray(pi_f), np.asarray(pi_u),
                                   rtol=1e-6, atol=1e-8)


class TestBaselineSolversThroughEngines:
    def test_power_through_block_ell(self):
        g = generators.tri_mesh(9, 11)
        eng = BlockEllEngine.from_graph(g, block=32, use_kernel=False)
        a = np.asarray(power(eng, 0.85, tol=1e-12, max_iter=2000).pi)
        b = np.asarray(power(device_graph(g), 0.85, tol=1e-12, max_iter=2000).pi)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)

    def test_forward_push_through_block_ell(self):
        g = generators.tri_mesh(9, 11)
        eng = FusedBlockEllEngine.from_graph(g, block=32, use_kernel=False)
        a = np.asarray(forward_push(eng, 0.85, rounds=40).pi)
        b = np.asarray(forward_push(device_graph(g), 0.85, rounds=40).pi)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)

    def test_power_low_precision_personalization(self):
        """Regression: the residual carry must follow p's dtype (the old code
        hardcoded float32 inf, which breaks non-f32 personalizations)."""
        g = generators.tri_mesh(9, 11)
        res = power(device_graph(g), 0.85, tol=1e-3,
                    p=jnp.ones((g.n,), jnp.bfloat16))
        pi = np.asarray(res.pi, np.float64)
        assert res.pi.dtype == jnp.bfloat16
        assert pi.sum() == pytest.approx(1.0, abs=2e-2)


class TestSelection:
    def test_as_engine_wraps_device_graph(self):
        g = generators.tri_mesh(5, 5)
        dg = device_graph(g)
        eng = as_engine(dg)
        assert isinstance(eng, CooEngine) and eng.dg is dg
        assert as_engine(eng) is eng
        with pytest.raises(TypeError):
            as_engine(g)

    def test_forced_modes(self):
        g = generators.tri_mesh(9, 11)
        assert select_engine(g, mode="coo").name == "coo"
        assert select_engine(g, mode="ell").name == "ell"
        assert select_engine(g, mode="block_ell").name == "block_ell"
        assert select_engine(g, mode="fused").name == "block_ell_fused"
        with pytest.raises(ValueError):
            select_engine(g, mode="nope")

    def test_auto_prefers_block_ell_on_clustered_graphs(self):
        dense = generators.caveman(20, 64, seed=0)   # near-dense tiles
        assert select_engine(dense, min_fill=0.05).name == "block_ell_fused"

    def test_auto_prefers_coo_on_scattered_graphs(self):
        # fill < 1%, max degree 5 over 2.09 directed edges a vertex: the
        # [5, n] table would be 2.39 slots an edge, past ELL's bar
        sparse = generators.kmer_chains(4_000, seed=0)  # fill < 1%
        assert select_engine(sparse, min_fill=0.05).name == "coo"

    @pytest.mark.parametrize("make, expected", [
        # power-law: one hub's degree sets every row's width
        (lambda: generators.powerlaw_ba(4_000, 3, seed=0), "coo"),
        # fill 1.5%, max degree 6 over 5.8: 1.03 slots an edge
        (lambda: generators.tri_mesh(40, 40), "ell"),
        (lambda: generators.kmer_chains(2_000, seed=1), "ell"),
    ], ids=["powerlaw", "mesh", "kmer_short"])
    def test_auto_under_fill_bar_picks_ell_or_coo(self, make, expected):
        """Under the fill bar, bounded-degree graphs take the ELL gather;
        graphs whose neighbour table would outgrow their edges stay COO."""
        assert select_engine(make(), min_fill=0.05).name == expected

    def test_auto_keeps_coo_when_ell_blocks_outgrow_memory(self,
                                                          monkeypatch):
        """The ELL round holds K gathered [n, B] blocks: past
        ELL_MAX_TEMP_SHARE of the device's memory the graph stays COO."""
        import repro.core.engine as engine_mod
        g = generators.tri_mesh(40, 40)        # K = 6: 4.9 MB at B = 128
        monkeypatch.setattr(engine_mod, "_device_memory_bytes",
                            lambda: 8_000_000)
        assert select_engine(g, batch=128, min_fill=0.05).name == "coo"
        assert select_engine(g, batch=8, min_fill=0.05).name == "ell"

    def test_auto_small_graph_stays_coo(self):
        tiny = generators.tri_mesh(5, 5)
        assert select_engine(tiny).name == "coo"

    def test_reuses_provided_device_graph(self):
        g = generators.kmer_chains(500, seed=1)
        dg = device_graph(g, pad_edges_to=2048)
        eng = select_engine(g, mode="coo", dg=dg)
        assert eng.dg is dg

    def test_fingerprint_tracks_content(self):
        from repro.core.engine import _graph_fingerprint
        g1, g2 = generators.tri_mesh(10, 10), generators.tri_mesh(10, 11)
        assert _graph_fingerprint(g1) == \
            _graph_fingerprint(generators.tri_mesh(10, 10))
        assert _graph_fingerprint(g1) != _graph_fingerprint(g2)

    @staticmethod
    def _count_probes(monkeypatch):
        """Empty the fill memo and count `block_fill_rate` calls."""
        import repro.core.engine as engine_mod
        calls = []
        real = engine_mod.block_fill_rate

        def counting(g_, block=128, **kw):
            calls.append(block)
            return real(g_, block=block, **kw)

        monkeypatch.setattr(engine_mod, "block_fill_rate", counting)
        monkeypatch.setattr(engine_mod, "_FILL_MEMO", {})
        return calls

    def test_auto_mode_probes_once_per_shape(self, monkeypatch):
        calls = self._count_probes(monkeypatch)
        g = generators.caveman(30, 64, seed=0)  # dense tiles, n >= 2*block
        m1 = heuristic_mode(g)
        m2 = heuristic_mode(g)
        assert m1 == m2
        assert len(calls) == 1   # second call served from the fill memo

    def test_auto_mode_uses_process_probe_cache(self, monkeypatch):
        """Registries share the process-wide fill memo: a second
        registration of the same graph, in another registry, skips the
        probe."""
        from repro.serve import GraphRegistry
        calls = self._count_probes(monkeypatch)
        g = generators.tri_mesh(20, 20)         # n >= 2*block: probed
        GraphRegistry().register("a", g)
        GraphRegistry().register("b", g)
        assert len(calls) == 1


class TestServeIntegration:
    def test_registry_caches_engine_per_epoch(self):
        from repro.serve import GraphRegistry
        reg = GraphRegistry(engine="fused")
        g = generators.tri_mesh(9, 11)
        rg = reg.register("g", g)
        eng0 = rg.engine
        assert eng0.name == "block_ell_fused"
        assert reg.get("g").engine is eng0      # cached, not rebuilt per get
        reg.apply_updates("g", insert=[(0, 90)])
        assert rg.engine is not eng0            # epoch bump rebuilds once
        assert rg.engine.name == "block_ell_fused"

    @pytest.mark.parametrize("mode", ["coo", "ell", "block_ell", "fused"])
    def test_service_answers_match_oracle_on_every_engine(self, mode):
        from repro.serve import GraphRegistry, PageRankService, PPRQuery
        g = generators.tri_mesh(8, 9)
        reg = GraphRegistry(engine=mode)
        reg.register("g", g)
        svc = PageRankService(reg, max_batch=4, cache_capacity=16,
                              max_top_k=8)
        seeds = (3, 40)
        res = svc.query("g", seeds, tol=1e-8, top_k=8)
        p = np.zeros(g.n)
        p[list(seeds)] = 0.5
        oracle = true_pagerank_dense(g, 0.85, p=p)
        assert set(res.indices.tolist()) == \
            set(np.argsort(-oracle, kind="stable")[:8].tolist())
        np.testing.assert_allclose(res.scores, oracle[res.indices],
                                   rtol=1e-4, atol=1e-6)

    def test_no_per_tick_engine_rebuild(self):
        from repro.serve import GraphRegistry, PageRankService, PPRQuery
        g = generators.tri_mesh(9, 11)
        reg = GraphRegistry(engine="block_ell")
        reg.register("g", g)
        svc = PageRankService(reg, max_batch=2, cache_capacity=16,
                              max_top_k=4)
        eng = reg.get("g").engine
        for i in range(5):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,), top_k=4))
        svc.run_until_drained()
        assert svc.stats["solves"] >= 2          # several ticks ran
        assert reg.get("g").engine is eng        # same engine object driven
