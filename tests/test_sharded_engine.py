"""ShardedEngine parity suite: the mesh-sharded engines must produce the
same PageRank as the single-device COO engine and the dense oracle.

Covers 1D and 2D partitions, vector [n] and matrix [n, B] personalization,
1/2/8-device meshes (cases needing more devices than the process has SKIP —
CI's tests-multidevice job and the tier-1 subprocess wrapper run with 8
fake devices, a plain single-device run still exercises the 1-device mesh),
the 2D column-layout round-trip, the select_engine device heuristic, and
the serving registry over sharded engines.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core import (cpaa, cpaa_adaptive, cpaa_fixed, make_schedule,
                        true_pagerank_dense)
from repro.core.engine import (CooEngine, Sharded1DEngine, Sharded2DEngine,
                               factor_grid, select_engine)
from repro.graph import generators
from repro.graph.ops import device_graph

GRAPHS = {
    "mesh": lambda: generators.tri_mesh(9, 11),
    "powerlaw": lambda: generators.powerlaw_ba(120, 3, seed=2),
    "kmer": lambda: generators.kmer_chains(200, seed=4),
}
DEV_COUNTS = (1, 2, 8)


def _devices(n_dev):
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} devices, have {jax.device_count()}")
    return np.asarray(jax.devices()[:n_dev])


def _engine(kind: str, g, n_dev: int):
    if kind == "1d":
        mesh = Mesh(_devices(n_dev), ("dev",))
        return Sharded1DEngine.from_graph(g, mesh=mesh, lane=4)
    r, c = factor_grid(n_dev)
    mesh = Mesh(_devices(n_dev).reshape(r, c), ("row", "col"))
    return Sharded2DEngine.from_graph(g, mesh=mesh, grid=(r, c), lane=4)


class TestShardedParity:
    @pytest.mark.parametrize("n_dev", DEV_COUNTS)
    @pytest.mark.parametrize("kind", ["1d", "2d"])
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_vector_matches_coo_and_oracle(self, gname, kind, n_dev):
        g = GRAPHS[gname]()
        eng = _engine(kind, g, n_dev)
        truth = true_pagerank_dense(g, 0.85)
        pi_coo = np.asarray(cpaa(CooEngine(device_graph(g)), 0.85, 1e-8).pi,
                            np.float64)
        pi = np.asarray(cpaa(eng, 0.85, 1e-8).pi, np.float64)
        assert pi.shape == (g.n,)
        assert np.abs(pi - pi_coo).sum() <= 1e-5          # L1 vs COO engine
        assert np.max(np.abs(pi - truth) / truth) < 5e-5  # vs dense oracle

    @pytest.mark.parametrize("n_dev", DEV_COUNTS)
    @pytest.mark.parametrize("kind", ["1d", "2d"])
    def test_batched_matches_coo(self, kind, n_dev):
        g = GRAPHS["mesh"]()
        eng = _engine(kind, g, n_dev)
        rng = np.random.default_rng(3)
        B = 4
        p = np.zeros((g.n, B), np.float32)
        for j in range(B):
            p[rng.choice(g.n, rng.integers(1, 4), replace=False), j] = 1.0
        pi_coo = np.asarray(cpaa(CooEngine(device_graph(g)), 0.85, 1e-8,
                                 p=jnp.asarray(p)).pi)
        pi = np.asarray(cpaa(eng, 0.85, 1e-8, p=jnp.asarray(p)).pi)
        assert pi.shape == (g.n, B)
        np.testing.assert_allclose(pi, pi_coo, rtol=1e-5, atol=1e-7)
        oracle = np.asarray(true_pagerank_dense(g, 0.85, p=p))
        np.testing.assert_allclose(pi, oracle, rtol=1e-4, atol=1e-7)

    def test_power_through_sharded(self):
        from repro.core import power
        g = GRAPHS["mesh"]()
        eng = _engine("1d", g, 1)
        a = np.asarray(power(eng, 0.85, tol=1e-12, max_iter=2000).pi)
        b = np.asarray(power(device_graph(g), 0.85, tol=1e-12,
                             max_iter=2000).pi)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


class TestAdaptiveSharded:
    """Residual-controlled CPAA on the mesh-sharded engines: the residual
    reduction is a cross-shard psum (the solve vectors are global sharded
    arrays), so parity with the single-device adaptive solve — and the
    a-priori round cap — must hold on every mesh shape."""

    @pytest.mark.parametrize("n_dev", DEV_COUNTS)
    @pytest.mark.parametrize("kind", ["1d", "2d"])
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_vector_matches_coo_and_cap(self, gname, kind, n_dev):
        g = GRAPHS[gname]()
        eng = _engine(kind, g, n_dev)
        res = cpaa_adaptive(eng, 0.85, 1e-8)
        ref = cpaa_adaptive(CooEngine(device_graph(g)), 0.85, 1e-8)
        pi = np.asarray(res.pi, np.float64)
        assert pi.shape == (g.n,)
        assert np.abs(pi - np.asarray(ref.pi, np.float64)).sum() <= 1e-5
        truth = true_pagerank_dense(g, 0.85)
        assert np.max(np.abs(pi - truth) / truth) < 5e-5
        assert res.iterations <= res.rounds_bound
        assert res.iterations == ref.iterations  # same exit round everywhere

    @pytest.mark.parametrize("n_dev", DEV_COUNTS)
    @pytest.mark.parametrize("kind", ["1d", "2d"])
    def test_batched_matches_coo_with_column_masks(self, kind, n_dev):
        g = GRAPHS["mesh"]()
        eng = _engine(kind, g, n_dev)
        rng = np.random.default_rng(5)
        B = 4
        p = np.zeros((g.n, B), np.float32)
        p[:, 0] = 1.0 / g.n             # broad column: converges early
        for j in range(1, B):
            p[rng.choice(g.n, rng.integers(1, 4), replace=False), j] = 1.0
        res = cpaa_adaptive(eng, 0.85, 1e-8, p=jnp.asarray(p))
        ref = cpaa_adaptive(CooEngine(device_graph(g)), 0.85, 1e-8,
                            p=jnp.asarray(p))
        np.testing.assert_allclose(np.asarray(res.pi), np.asarray(ref.pi),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_array_equal(res.column_rounds, ref.column_rounds)
        assert int(res.column_rounds.max()) <= res.rounds_bound

    @pytest.mark.parametrize("kind", ["1d", "2d"])
    def test_distributed_builders_adaptive_mode(self, kind):
        """The historical array-passing builders accept adaptive=True and
        agree with the fixed-round builders at the same operating point."""
        from repro.core.distributed import (cpaa_distributed_1d,
                                            cpaa_distributed_2d,
                                            col_layout_perm,
                                            pad_personalization,
                                            put_partition_1d,
                                            put_partition_2d)
        from repro.graph.partition import partition_1d, partition_2d
        g = GRAPHS["mesh"]()
        n_dev = min(2, jax.device_count())
        sched = make_schedule(0.85, 1e-8)
        if kind == "1d":
            mesh = Mesh(_devices(n_dev), ("dev",))
            part = partition_1d(g, n_dev, lane=4)
            arrs = put_partition_1d(part, mesh, ("dev",))
            p = pad_personalization(np.full(g.n, 1.0 / g.n, np.float32),
                                    part.n)
            fn_a = cpaa_distributed_1d(mesh, ("dev",), part, sched,
                                       adaptive=True)
            fn_f = cpaa_distributed_1d(mesh, ("dev",), part, sched)
            pi_a = np.asarray(fn_a(p, *arrs), np.float64)[: g.n]
            pi_f = np.asarray(fn_f(p, *arrs), np.float64)[: g.n]
        else:
            r, c = factor_grid(n_dev)
            mesh = Mesh(_devices(n_dev).reshape(r, c), ("row", "col"))
            part = partition_2d(g, (r, c), lane=4)
            arrs = put_partition_2d(part, mesh, "row", "col")
            perm = col_layout_perm(part.n, part.grid)
            p_col = pad_personalization(
                np.full(g.n, 1.0 / g.n, np.float32), part.n)[perm]
            fn_a = cpaa_distributed_2d(mesh, "row", "col", part, sched,
                                       adaptive=True)
            fn_f = cpaa_distributed_2d(mesh, "row", "col", part, sched)
            pi_a = np.asarray(fn_a(p_col, *arrs), np.float64)
            pi_f = np.asarray(fn_f(p_col, *arrs), np.float64)
        assert np.abs(pi_a - pi_f).sum() <= 1e-5


class TestShardedLayout:
    @pytest.mark.parametrize("kind", ["1d", "2d"])
    def test_to_from_internal_is_identity(self, kind):
        g = GRAPHS["powerlaw"]()
        n_dev = min(2, jax.device_count())
        eng = _engine(kind, g, n_dev)
        assert eng.n == g.n and eng.n_pad >= g.n
        for shape in [(g.n,), (g.n, 5)]:
            x = jnp.asarray(np.random.default_rng(0).random(shape),
                            jnp.float32)
            np.testing.assert_array_equal(
                np.asarray(eng.from_internal(eng.to_internal(x))),
                np.asarray(x))

    @pytest.mark.parametrize("kind", ["1d", "2d"])
    def test_apply_matches_coo_spmv(self, kind):
        from repro.graph.ops import spmv
        g = GRAPHS["mesh"]()
        n_dev = min(2, jax.device_count())
        eng = _engine(kind, g, n_dev)
        x = jax.random.normal(jax.random.PRNGKey(2), (g.n,), jnp.float32)
        y = eng.from_internal(eng.apply(eng.to_internal(x)))
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(spmv(device_graph(g), x)),
                                   rtol=2e-4, atol=1e-5)

    def test_2d_hlo_uses_reduce_scatter(self):
        if jax.device_count() < 2:
            pytest.skip("collectives degenerate on one device")
        g = GRAPHS["mesh"]()
        eng = _engine("2d", g, min(8, jax.device_count()))
        sched = make_schedule(0.85, rounds=8)
        coeffs = jnp.asarray(sched.coeffs, jnp.float32)
        p = jnp.ones((g.n,), jnp.float32)
        txt = jax.jit(lambda e, c, x: cpaa_fixed(e, c, x, rounds=8)) \
            .lower(eng, coeffs, p).compile().as_text()
        assert "reduce-scatter" in txt


class TestShardedSelection:
    def test_forced_modes_and_dash_aliases(self):
        g = GRAPHS["mesh"]()
        assert select_engine(g, mode="sharded_1d", lane=4).name == "sharded_1d"
        assert select_engine(g, mode="sharded-1d", lane=4).name == "sharded_1d"
        assert select_engine(g, mode="sharded-2d", lane=4).name == "sharded_2d"

    def test_auto_stays_single_device_below_threshold(self):
        # test graphs are far below SHARDED_MIN_N: the single-device
        # fill-rate logic must be untouched even on a multi-device process
        assert select_engine(generators.tri_mesh(5, 5)).name == "coo"

    def test_auto_below_threshold_takes_ell_on_bounded_degree(self):
        # the same holds past the small-graph bar: a mesh under the fill
        # bar takes the single-device ELL gather, not a sharded engine
        assert select_engine(generators.tri_mesh(40, 40)).name == "ell"

    @pytest.mark.parametrize("n_dev, axes, expected", [
        (2, ("dev",), "sharded_1d"),
        (4, ("row", "col"), "sharded_2d"),
        (1, ("dev",), "ell"),
    ])
    def test_auto_shards_bounded_degree_graphs_by_device_count(
            self, n_dev, axes, expected):
        """The device axis is decided before the format: a mesh-like graph
        that would take the ELL gather on one device shards on several.
        `heuristic_mode` reads only the mesh's size and axis names, so a
        stand-in decides here without that many devices."""
        from types import SimpleNamespace
        from repro.core.engine import heuristic_mode
        mesh = SimpleNamespace(devices=np.empty(n_dev), axis_names=axes)
        g = generators.tri_mesh(40, 40)      # n = 1600
        assert heuristic_mode(g, mesh=mesh, sharded_min_n=256) == expected

    def test_auto_shards_large_graphs_on_multi_device(self):
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        g = GRAPHS["mesh"]()  # n = 99; lower the bar instead of building 64k
        picked = select_engine(g, sharded_min_n=16, lane=4)  # 99 >= 4 * 16
        expected = "sharded_2d" if jax.device_count() >= 4 else "sharded_1d"
        assert picked.name == expected

    def test_auto_picks_1d_between_bars(self):
        if jax.device_count() < 4:
            pytest.skip("needs >= 4 devices")
        g = GRAPHS["mesh"]()  # n = 99 >= thr but < 4 * thr -> 1D
        assert select_engine(g, sharded_min_n=50, lane=4).name == "sharded_1d"


class TestShardedServe:
    @pytest.mark.parametrize("mode", ["sharded-1d", "sharded-2d"])
    def test_service_answers_match_oracle(self, mode):
        from repro.serve import GraphRegistry, PageRankService
        g = generators.tri_mesh(8, 9)
        reg = GraphRegistry(engine=mode, partition_lane=4)
        reg.register("g", g)
        assert reg.get("g").engine.name == mode.replace("-", "_")
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

    def test_epoch_bump_rebuilds_partition(self):
        from repro.serve import GraphRegistry
        g = generators.tri_mesh(9, 11)
        reg = GraphRegistry(engine="sharded-1d", partition_lane=4)
        rg = reg.register("g", g)
        eng0 = rg.engine
        reg.apply_updates("g", insert=[(0, 90)])
        assert rg.engine is not eng0 and rg.engine.name == "sharded_1d"
