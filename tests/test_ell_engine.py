"""EllEngine tests: parity with COO and the dense float64 oracle, the
sentinel padding and mass invariant, the pytree and jit contract, refresh
under registry churn, and a compiled solve that holds no scatter."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_schedule, true_pagerank_dense
from repro.core.engine import (CooEngine, EllEngine, apply_counts,
                               reset_apply_counts)
from repro.core.pagerank import cpaa_fixed
from repro.graph import generators
from repro.graph.ops import device_graph
from repro.graph.structure import Graph


# a scatter op in StableHLO (lowered) or HLO (compiled) text; names and
# metadata that merely mention the word do not match
SCATTER_OP = re.compile(r"stablehlo\.scatter|= \S+ scatter\(")


def _relabelled_mesh():
    """tri_mesh under a random vertex relabelling: no grid-offset
    structure survives in the neighbour ids."""
    g = generators.tri_mesh(12, 13)
    perm = np.random.default_rng(7).permutation(g.n)
    return Graph.from_undirected_edges(g.n, perm[g.src], perm[g.dst])


def _with_isolated():
    """A ring of 30 vertices plus 6 isolated ones (self-loop slots)."""
    u = np.arange(30)
    return Graph.from_undirected_edges(36, u, (u + 1) % 30)


GRAPHS = {
    "tri_mesh": lambda: generators.tri_mesh(9, 11),
    "kmer": lambda: generators.kmer_chains(300, seed=4),
    "relabelled_mesh": _relabelled_mesh,
    "isolated": _with_isolated,
}


def _dense_p(g):
    a = np.zeros((g.n, g.n))
    np.add.at(a, (g.dst, g.src), 1.0)
    return a / np.maximum(a.sum(axis=0), 1.0)[None, :]


def _personalization(n, b):
    rng = np.random.default_rng(3)
    if b == 1:
        return np.full(n, 1.0 / n)
    p = np.zeros((n, b))
    for j in range(b):
        p[rng.choice(n, rng.integers(1, 4), replace=False), j] = 1.0
    return p


def _solve(eng, p, tol=1e-8):
    sched = make_schedule(0.85, tol)
    pi, _ = cpaa_fixed(eng, jnp.asarray(sched.coeffs, jnp.float32),
                       jnp.asarray(p, jnp.float32), rounds=sched.rounds)
    return np.asarray(pi, np.float64)


def _l1(a, b):
    return float(np.abs(a - b).sum(axis=0).max())


class TestParity:
    @pytest.mark.parametrize("b", [1, 8])
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_f32_matches_coo_and_oracle(self, gname, b):
        g = GRAPHS[gname]()
        p = _personalization(g.n, b)
        ell = _solve(EllEngine.from_graph(g), p)
        assert _l1(ell, true_pagerank_dense(g, 0.85, p=p)) <= 1e-6
        assert _l1(ell, _solve(CooEngine(device_graph(g)), p)) <= 1e-6

    @pytest.mark.parametrize("b", [1, 8])
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_bf16_inv_deg_matches_bf16_coo(self, gname, b):
        """Packed bf16 1/deg: the engine matches COO holding the same packed
        weights to f32 rounding. Against the float64 oracle both carry the
        storage rounding (up to 2^-9 per weight, amplified at most
        c / (1 - c) = 5.7x), which reads 1.2e-3 to 5.6e-3 on these graphs."""
        g = GRAPHS[gname]()
        p = _personalization(g.n, b)
        eng = EllEngine.from_graph(g, weight_dtype=jnp.bfloat16)
        assert eng.weight_dtype == jnp.bfloat16 and eng.dtype == jnp.float32
        ell = _solve(eng, p)
        coo = _solve(CooEngine(device_graph(g, weight_dtype=jnp.bfloat16)), p)
        assert _l1(ell, coo) <= 1e-6
        assert _l1(ell, true_pagerank_dense(g, 0.85, p=p)) <= 1e-2

    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_apply_matches_dense_transition(self, gname):
        g = GRAPHS[gname]()
        x = np.random.default_rng(1).random((g.n, 3))
        y = np.asarray(EllEngine.from_graph(g).apply(
            jnp.asarray(x, jnp.float32)), np.float64)
        np.testing.assert_allclose(y, _dense_p(g) @ x, rtol=1e-5, atol=1e-7)


class TestTable:
    def test_sentinel_padding_gathers_zero(self):
        g = _with_isolated()
        eng = EllEngine.from_graph(g)
        nbr = np.asarray(eng.nbr)
        assert nbr.shape == (2, g.n)          # ring degree 2, self loop 1
        # each isolated vertex holds its self loop and one sentinel slot
        np.testing.assert_array_equal(nbr[:, 30:],
                                      [np.arange(30, 36), np.full(6, g.n)])
        assert eng.slot_fill == pytest.approx(g.m / (2 * g.n))
        # a vector that is zero everywhere but the isolated vertices maps
        # to itself: the sentinel adds exactly nothing
        x = np.zeros(g.n, np.float32)
        x[30:] = np.arange(1, 7)
        np.testing.assert_array_equal(np.asarray(eng.apply(jnp.asarray(x))),
                                      x)

    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    def test_mass_preserved(self, gname):
        g = GRAPHS[gname]()
        x = np.random.default_rng(2).random((g.n, 4)).astype(np.float32)
        y = np.asarray(EllEngine.from_graph(g).apply(jnp.asarray(x)))
        np.testing.assert_allclose(y.sum(axis=0), x.sum(axis=0), rtol=1e-5)

    def test_unsorted_edge_list_builds_the_same_table(self):
        g = _relabelled_mesh()
        order = np.random.default_rng(5).permutation(g.m)
        shuffled = Graph(n=g.n, src=g.src[order], dst=g.dst[order])
        a = np.sort(np.asarray(EllEngine.from_graph(g).nbr), axis=0)
        b = np.sort(np.asarray(EllEngine.from_graph(shuffled).nbr), axis=0)
        np.testing.assert_array_equal(a, b)

    def test_min_width_pads_with_sentinels(self):
        g = generators.tri_mesh(5, 6)
        eng = EllEngine.from_graph(g, min_width=9)
        assert eng.width == 9
        assert np.all(np.asarray(eng.nbr)[6:] == g.n)


class TestJit:
    def test_pytree_round_trip(self):
        eng = EllEngine.from_graph(generators.tri_mesh(6, 7),
                                   weight_dtype=jnp.bfloat16)
        leaves, treedef = jax.tree_util.tree_flatten(eng)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(back, EllEngine) and back.n == eng.n
        assert back.dtype == eng.dtype and back.weight_dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(back.nbr),
                                      np.asarray(eng.nbr))

    def test_no_retrace_across_calls(self):
        g = generators.tri_mesh(8, 9)
        eng = EllEngine.from_graph(g)
        solve = jax.jit(lambda e, x: e.apply(x))
        x = jnp.ones((g.n, 4), jnp.float32)
        reset_apply_counts()
        solve(eng, x).block_until_ready()
        first = apply_counts()["ell"]
        for _ in range(3):
            solve(eng, x).block_until_ready()
        # a same-shape rebuild is a new pytree with the same treedef
        solve(EllEngine.from_graph(g), x).block_until_ready()
        assert apply_counts()["ell"] == first == 1

    def test_solve_holds_no_scatter(self):
        g = _relabelled_mesh()
        eng = EllEngine.from_graph(g)
        sched = make_schedule(0.85, 1e-6)
        coeffs = jnp.asarray(sched.coeffs, jnp.float32)
        for p in (jnp.ones((g.n,), jnp.float32),
                  jnp.ones((g.n, 8), jnp.float32)):
            lowered = cpaa_fixed.lower(eng, coeffs, p, rounds=sched.rounds)
            assert not SCATTER_OP.search(lowered.as_text())
            assert not SCATTER_OP.search(lowered.compile().as_text())
        # the pattern does find COO's segment_sum, at both levels
        coo = cpaa_fixed.lower(CooEngine(device_graph(g)), coeffs, p,
                               rounds=sched.rounds)
        assert SCATTER_OP.search(coo.as_text())
        assert SCATTER_OP.search(coo.compile().as_text())


class TestRegistryChurn:
    def test_refresh_equals_rebuild_under_churn(self):
        from repro.obs.metrics import MetricsRegistry
        from repro.serve import GraphRegistry
        g = _relabelled_mesh()
        reg = GraphRegistry(engine="ell")
        metrics = MetricsRegistry()
        reg.bind_metrics(metrics)
        rg = reg.register("g", g)
        assert rg.engine.name == "ell"
        width0 = rg.engine.width
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.random((g.n, 3)), jnp.float32)
        for step in range(4):
            host = reg.get("g").host
            keys = np.unique(np.minimum(host.src, host.dst).astype(np.int64)
                             * g.n + np.maximum(host.src, host.dst))
            keys = keys[(keys // g.n) != (keys % g.n)]
            gone = rng.choice(keys, 5, replace=False)
            delete = [(int(k // g.n), int(k % g.n)) for k in gone]
            u, v = rng.choice(g.n, 2, replace=False)
            reg.apply_updates("g", insert=[(int(u), int(v))], delete=delete)
            rg = reg.get("g")
            assert rg.epoch == step + 1 and rg.engine.name == "ell"
            assert rg.engine.width >= width0   # deletes never shrink it
            fresh = EllEngine.from_graph(rg.host)
            np.testing.assert_allclose(np.asarray(rg.engine.apply(x)),
                                       np.asarray(fresh.apply(x)),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(
                np.asarray(rg.engine.apply(x)),
                np.asarray(CooEngine(device_graph(rg.host)).apply(x)),
                rtol=1e-5, atol=1e-7)
        fill = metrics.get("graph_engine_slot_fill").labels(graph="g").value
        assert fill == pytest.approx(rg.engine.slot_fill)
        assert 0.0 < fill <= 1.0


def _registry(g, **kw):
    from repro.obs.metrics import MetricsRegistry
    from repro.serve import GraphRegistry
    reg = GraphRegistry(engine="ell", **kw)
    metrics = MetricsRegistry()
    reg.bind_metrics(metrics)
    reg.register("g", g)
    return reg, metrics


def _assert_table_matches_rebuild(eng, g):
    """Column by column the patched table holds the same neighbours as a
    fresh build of g (slot order may differ), device and host copies
    agree, and inv_deg is the fresh one."""
    fresh = EllEngine.from_graph(g, min_width=eng.width)
    assert eng.width == fresh.width
    got = np.asarray(eng.nbr)
    np.testing.assert_array_equal(got, eng._host_nbr)
    np.testing.assert_array_equal(np.sort(got, axis=0),
                                  np.sort(np.asarray(fresh.nbr), axis=0))
    np.testing.assert_array_equal(np.asarray(eng.inv_deg),
                                  np.asarray(fresh.inv_deg))
    assert eng.live_slots == g.m


class TestInPlacePatch:
    def test_patch_keeps_shape_object_and_compiled_solve(self):
        """A batch that fits the table is a scatter into the same engine:
        no host Graph is materialized, the width stays, and a jitted apply
        does not retrace."""
        g = generators.tri_mesh(40, 40)          # K = 6 on interior vertices
        reg, _ = _registry(g)
        rg = reg.get("g")
        eng = rg.engine
        solve = jax.jit(lambda e, x: e.apply(x))
        x = jnp.asarray(np.random.default_rng(0).random((g.n, 2)),
                        jnp.float32)
        reset_apply_counts()
        solve(eng, x).block_until_ready()
        # free a slot of vertex 41 and of a far vertex, then join the two
        far = 41 + 20 * 40
        tab = np.asarray(eng.nbr)
        reg.apply_updates("g", delete=[(41, int(tab[0, 41])),
                                       (far, int(tab[0, far]))])
        reg.apply_updates("g", insert=[(41, far)])
        rg = reg.get("g")
        assert rg.last_update_incremental and rg._host_stale
        assert rg.engine is eng and eng.width == 6
        solve(eng, x).block_until_ready()
        assert apply_counts()["ell"] == 1
        _assert_table_matches_rebuild(eng, rg.host)
        np.testing.assert_allclose(
            np.asarray(solve(eng, x)),
            np.asarray(CooEngine(device_graph(rg.host)).apply(x)),
            rtol=1e-6, atol=1e-7)

    def test_overflow_widens_by_a_quarter(self):
        g = generators.tri_mesh(40, 40)
        reg, _ = _registry(g)
        hub = 41 + 10 * 40                       # interior: degree 6
        reg.apply_updates("g", insert=[(hub, hub + 7)])
        eng = reg.get("g").engine
        assert eng.name == "ell" and eng.width == 8   # ceil(6 * 5 / 4)
        assert np.asarray(eng.nbr).shape == (8, g.n)
        _assert_table_matches_rebuild(eng, reg.get("g").host)
        # a need past the quarter takes the need itself
        reg.apply_updates("g", insert=[(hub, hub + 100 + i)
                                       for i in range(4)])
        eng = reg.get("g").engine
        assert eng.width == 11                   # 6 + 1 + 4 > ceil(8 * 5/4)
        _assert_table_matches_rebuild(eng, reg.get("g").host)

    def test_table_past_the_bar_falls_back_to_coo(self):
        """A vertex that grows into a hub makes n * K outgrow the edges:
        the graph moves to COO on the registry's patched DeviceGraph, and
        the slot-fill gauge drops to 0."""
        g = generators.tri_mesh(40, 40)
        reg, metrics = _registry(g)
        reg.apply_updates("g", insert=[(0, v) for v in range(50, 90)])
        rg = reg.get("g")
        assert rg.engine.name == "coo" and rg.engine.dg is rg.dg
        assert metrics.get("graph_engine_slot_fill").labels(
            graph="g").value == 0.0
        x = jnp.asarray(np.random.default_rng(1).random((g.n, 2)),
                        jnp.float32)
        np.testing.assert_allclose(
            np.asarray(rg.engine.apply(x)),
            np.asarray(CooEngine(device_graph(rg.host)).apply(x)),
            rtol=1e-6, atol=1e-7)

    def test_isolation_round_trip_moves_the_self_loop(self):
        """Deleting every edge of a vertex gives it the self-loop slot;
        re-inserting one takes the loop away again."""
        g = _with_isolated()
        reg, _ = _registry(g)
        reg.apply_updates("g", delete=[(4, 5), (5, 6)])
        eng = reg.get("g").engine
        assert 5 in np.asarray(eng.nbr)[:, 5]
        _assert_table_matches_rebuild(eng, reg.get("g").host)
        reg.apply_updates("g", insert=[(5, 31), (30, 31)])
        eng = reg.get("g").engine
        assert 5 not in np.asarray(eng.nbr)[:, 5]
        assert 30 not in np.asarray(eng.nbr)[:, 30]
        _assert_table_matches_rebuild(eng, reg.get("g").host)

    @pytest.mark.parametrize("weight_dtype", [None, jnp.bfloat16])
    def test_random_churn_matches_rebuild(self, weight_dtype):
        g = _relabelled_mesh()
        reg, _ = _registry(g, weight_dtype=weight_dtype)
        rng = np.random.default_rng(4)
        for _ in range(6):
            keys = reg.get("g").keys
            gone = rng.choice(keys, 4, replace=False)
            u, v = rng.integers(0, g.n, (2, 3))
            reg.apply_updates(
                "g", delete=[(int(k // g.n), int(k % g.n)) for k in gone],
                insert=[(int(a), int(b)) for a, b in zip(u, v) if a != b])
            rg = reg.get("g")
            assert rg.engine.name == "ell"
            fresh = EllEngine.from_graph(rg.host, weight_dtype=weight_dtype,
                                         min_width=rg.engine.width)
            np.testing.assert_array_equal(np.asarray(rg.engine.inv_deg),
                                          np.asarray(fresh.inv_deg))
            np.testing.assert_array_equal(
                np.sort(np.asarray(rg.engine.nbr), axis=0),
                np.sort(np.asarray(fresh.nbr), axis=0))

    def test_without_host_table_refresh_rebuilds(self):
        """An engine rebuilt from pytree leaves, or built on a graph with a
        self loop beside real edges, has no host table: refresh rebuilds
        from the graph it is handed, and refuses without one."""
        from repro.graph.structure import edge_delta
        g = generators.tri_mesh(6, 7)
        leaves, treedef = jax.tree_util.tree_flatten(EllEngine.from_graph(g))
        bare = jax.tree_util.tree_unflatten(treedef, leaves)
        keys = np.unique(np.minimum(g.src, g.dst).astype(np.int64) * g.n
                         + np.maximum(g.src, g.dst))
        delta = edge_delta(g.n, keys, delete_keys=keys[:1])
        with pytest.raises(ValueError):
            bare.refresh(None, delta)
        g2 = Graph.from_undirected_edges(g.n, keys[1:] // g.n,
                                         keys[1:] % g.n)
        new = bare.refresh(g2, delta)
        assert new is not bare and new.live_slots == g2.m
        looped = Graph(n=3, src=np.array([0, 1, 1]), dst=np.array([1, 0, 1]))
        assert EllEngine.from_graph(looped)._host_nbr is None
