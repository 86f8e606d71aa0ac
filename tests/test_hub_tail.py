"""HubTailEngine tests: parity vs COO, degenerate splits, auto-selection,
packed bf16 weights, serving integration, the Grolmusz degree-prior
oracle at paper scale, and the sliced tail over the internal order."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_schedule
from repro.core.engine import (HUB_TAIL_MIN_N, CooEngine, HubTailEngine,
                               apply_counts, reset_apply_counts,
                               select_engine)
from repro.core.pagerank import cpaa_fixed, degree_prior
from repro.graph import generators
from repro.graph.datasets import chung_lu
from repro.graph.ops import device_graph
from repro.graph.structure import Graph


def _pagerank(eng, g, rounds=None, p=None):
    """Normalized CPAA PageRank through an engine (the parity yardstick:
    raw spmv maxabs is accumulation-order noise on big hub rows)."""
    sched = make_schedule(0.85, 1e-6)
    coeffs = jnp.asarray(sched.coeffs, jnp.float32)
    if p is None:
        p = jnp.full((g.n,), 1.0 / g.n, jnp.float32)
    pi, _ = cpaa_fixed(eng, coeffs, p,
                       rounds=sched.rounds if rounds is None else rounds)
    return pi


@pytest.fixture(scope="module")
def skewed():
    return chung_lu(20_000, avg_deg=16.0, seed=1)


class TestParity:
    def test_f32_matches_coo(self, skewed):
        g = skewed
        ref = _pagerank(CooEngine(device_graph(g)), g)
        ht = _pagerank(HubTailEngine.from_graph(g), g)
        assert float(jnp.abs(ht - ref).sum()) <= 1e-5

    def test_bf16_weights_within_rounding(self, skewed):
        g = skewed
        ref = _pagerank(CooEngine(device_graph(g)), g)
        eng = HubTailEngine.from_graph(g, weight_dtype=jnp.bfloat16)
        assert eng.weight_dtype == jnp.bfloat16
        assert eng.dtype == jnp.float32    # solve dtype stays f32
        ht = _pagerank(eng, g)
        assert ht.dtype == jnp.float32
        assert float(jnp.abs(ht - ref).sum()) <= 1e-3

    def test_batched_personalizations(self, skewed):
        g = skewed
        rng = np.random.default_rng(0)
        p = rng.random((g.n, 4)).astype(np.float32)
        p /= p.sum(0, keepdims=True)
        p = jnp.asarray(p)
        ref = _pagerank(CooEngine(device_graph(g)), g, p=p)
        ht = _pagerank(HubTailEngine.from_graph(g), g, p=p)
        assert float(jnp.abs(ht - ref).sum(0).max()) <= 1e-5

    def test_mass_preserved(self, skewed):
        """P is column-stochastic; the sentinel-row trick must not leak
        mass into (or out of) the padding."""
        g = skewed
        eng = HubTailEngine.from_graph(g)
        x = jnp.asarray(np.random.default_rng(1).random(g.n, np.float32))
        y = eng.apply(x)
        assert y.shape == (g.n,)
        np.testing.assert_allclose(float(y.sum()), float(x.sum()), rtol=1e-5)

    @pytest.mark.parametrize("hub_min_deg", [1, 10**9])
    def test_degenerate_splits(self, hub_min_deg):
        """All-hub (every vertex panelized) and no-hub (pure tail
        slices) are both just P — the split point is a perf knob,
        never a correctness one."""
        g = generators.powerlaw_ba(2_000, m_attach=4, seed=2)
        ref = _pagerank(CooEngine(device_graph(g)), g)
        eng = HubTailEngine.from_graph(g, hub_min_deg=hub_min_deg)
        if hub_min_deg == 1:
            assert eng.n_hubs == g.n
        else:
            assert eng.n_hubs == 0
        ht = _pagerank(eng, g)
        assert float(jnp.abs(ht - ref).sum()) <= 1e-5


class TestEngineContract:
    def test_pytree_round_trip(self, skewed):
        eng = HubTailEngine.from_graph(skewed)
        leaves, treedef = jax.tree_util.tree_flatten(eng)
        eng2 = jax.tree_util.tree_unflatten(treedef, leaves)
        x = jnp.asarray(
            np.random.default_rng(0).random(skewed.n, np.float32))
        np.testing.assert_array_equal(np.asarray(eng.apply(x)),
                                      np.asarray(eng2.apply(x)))

    def test_jit_no_retrace(self, skewed):
        """The engine rides through jit as a pytree argument: new data,
        same treedef -> no retrace (apply_counts counts trace-time calls)."""
        eng = HubTailEngine.from_graph(skewed)
        f = jax.jit(lambda e, x: e.apply(x))
        reset_apply_counts()
        x = jnp.asarray(
            np.random.default_rng(0).random(skewed.n, np.float32))
        jax.block_until_ready(f(eng, x))
        jax.block_until_ready(f(eng, x + 1.0))
        leaves, treedef = jax.tree_util.tree_flatten(eng)
        eng2 = jax.tree_util.tree_unflatten(treedef, leaves)
        jax.block_until_ready(f(eng2, x))
        assert apply_counts().get("hub_tail", 0) == 1

    def test_refresh_rebuilds_current_graph(self):
        g = generators.powerlaw_ba(3_000, m_attach=4, seed=0)
        eng = HubTailEngine.from_graph(g, weight_dtype=jnp.bfloat16)
        g2 = generators.powerlaw_ba(3_000, m_attach=5, seed=1)
        eng2 = eng.refresh(g2)
        assert eng2.n == g2.n
        assert eng2.weight_dtype == jnp.bfloat16   # knobs survive refresh
        ref = _pagerank(CooEngine(device_graph(g2)), g2)
        assert float(jnp.abs(_pagerank(eng2, g2) - ref).sum()) <= 1e-3

    def test_select_engine_forced_and_auto(self):
        # forced, dash alias included (the CLI spells it hub-tail)
        g = generators.powerlaw_ba(2_000, m_attach=4, seed=0)
        assert select_engine(g, mode="hub-tail").name == "hub_tail"
        # auto: a large skewed graph crosses both thresholds
        big = chung_lu(HUB_TAIL_MIN_N, avg_deg=16.0, seed=0)
        assert isinstance(select_engine(big, mode="auto"), HubTailEngine)
        # ... a mesh has no hubs at all, so auto must NOT pick the split
        mesh = generators.tri_mesh(40, 40)
        assert not isinstance(select_engine(mesh, mode="auto"),
                              HubTailEngine)


class TestServing:
    def test_registry_hub_tail_bf16_with_updates(self):
        from repro.serve import GraphRegistry, PageRankService
        g = generators.powerlaw_ba(2_000, m_attach=4, seed=3)
        reg = GraphRegistry(engine="hub_tail", weight_dtype="bfloat16")
        reg.register("g", g)
        assert reg.get("g").engine.name == "hub_tail"
        assert reg.get("g").engine.weight_dtype == jnp.bfloat16
        svc = PageRankService(reg, max_batch=4, cache_capacity=16,
                              max_top_k=8)
        res = svc.query("g", (7,), tol=1e-6, top_k=8)
        assert res.scores.shape == (8,)
        assert np.all(np.isfinite(res.scores))
        # update path: the refresh must keep the engine class and knobs
        reg.apply_updates("g", insert=[(0, 1500)])
        eng = reg.get("g").engine
        assert eng.name == "hub_tail" and eng.weight_dtype == jnp.bfloat16
        res2 = svc.query("g", (7,), tol=1e-6, top_k=8)
        assert np.all(np.isfinite(res2.scores))


class TestDegreePriorOracle:
    def test_prior_is_stationary_at_scale(self):
        """Grolmusz: on an undirected graph deg/2m is EXACTLY stationary
        for P = A D^-1, so PageRank personalized at the degree prior
        returns the prior at any damping — an analytic oracle that needs
        no dense reference and therefore scales to n = 10^5."""
        g = chung_lu(100_000, avg_deg=16.0, seed=0)
        prior = degree_prior(g)
        np.testing.assert_allclose(prior.sum(), 1.0, rtol=1e-12)
        p = jnp.asarray(prior, jnp.float32)
        for eng in (CooEngine(device_graph(g)),
                    HubTailEngine.from_graph(g)):
            pi = _pagerank(eng, g, p=p)
            assert float(jnp.abs(pi - p).sum()) <= 1e-3


def _kronecker(scale, seed):
    """The benchmark's Graph 500 draw (A, B, C = 0.57, 0.19, 0.19,
    edgefactor 16), duplicates, self loops and isolated vertices kept in
    the edge list, as the Graph the program builds from it."""
    import importlib.util
    from pathlib import Path
    from repro.graph.structure import Graph
    path = Path(__file__).resolve().parents[1] / "bench" / "graphs" / \
        "kronecker.py"
    spec = importlib.util.spec_from_file_location("bench_kronecker", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    n, u, v = mod.make(seed, scale, 16, 0.57, 0.19, 0.19)
    assert np.any(u == v)                     # the draw holds self loops
    return Graph.from_undirected_edges(n, u, v)


def _isolated(g):
    """Vertices whose one edge is the self loop the Graph gave them."""
    return np.unique(g.src[g.src == g.dst])


@pytest.fixture(scope="module")
def kron12():
    return _kronecker(12, seed=12)


@pytest.fixture(scope="module")
def kron17():
    return _kronecker(17, seed=17)


def _l1_vs_dense(g, p, dtype=jnp.float32):
    """Per-column L1 of cpaa_fixed through a forced HubTailEngine at
    (c, tol) = (0.85, 1e-6) against the float64 direct solve."""
    from repro.core.pagerank import true_pagerank_dense
    eng = HubTailEngine.from_graph(g, dtype=dtype)
    assert eng.n_hubs > 0 and eng.hub_edge_share > 0.5
    sched = make_schedule(0.85, 1e-6)
    pi, _ = cpaa_fixed(eng, jnp.asarray(sched.coeffs, dtype),
                       jnp.asarray(p, dtype), rounds=sched.rounds)
    ref = true_pagerank_dense(g, 0.85, p=p)
    return np.abs(np.asarray(pi, np.float64) - ref).sum(axis=0)


# Uniform p: Formula 8's 24 rounds truncate the series by under 1e-6 in
# L1, and float32 rounding over 24 rounds adds under another 1e-6 (the
# f32 solve reads 2.5e-7 on this draw; a bf16 iterate reads 4.0e-3).
GLOBAL_L1_TOL = 2e-6
# One-vertex seeds: the truncation is larger on a seed of low degree,
# 7.3e-6 on this draw's random seed of degree 12 (the same series reads
# the same in float64), so the bar is 2e-5 (a bf16 iterate reads 5.4e-3
# on the hub's column and 6.5e-3 on that seed's).
SEEDED_L1_TOL = 2e-5


def _seeded_p(g):
    """[n, 4] one-hot columns: the largest hub, an isolated vertex and two
    random vertices."""
    rng = np.random.default_rng(3)
    seeds = [int(np.argmax(g.deg)), int(_isolated(g)[0]),
             *map(int, rng.choice(g.n, 2, replace=False))]
    p = np.zeros((g.n, 4))
    p[seeds, np.arange(4)] = 1.0
    return p


class TestKronecker:
    """Hub/tail on the Graph 500 law against a float64 reference."""

    def test_global_matches_dense_reference(self, kron12):
        p = np.full(kron12.n, 1.0 / kron12.n)
        assert float(_l1_vs_dense(kron12, p)) <= GLOBAL_L1_TOL

    def test_seeded_columns_match_dense_reference(self, kron12):
        err = _l1_vs_dense(kron12, _seeded_p(kron12))
        assert err.shape == (4,)
        assert np.all(err <= SEEDED_L1_TOL), err
        # an isolated seed keeps all its mass through its self loop
        assert err[1] <= 1e-7

    def test_bf16_iterate_fails_the_tolerances(self, kron12):
        """The tolerances tell float32 from one precision step below."""
        p = np.full(kron12.n, 1.0 / kron12.n)
        assert float(_l1_vs_dense(kron12, p, jnp.bfloat16)) > GLOBAL_L1_TOL
        err = _l1_vs_dense(kron12, _seeded_p(kron12), jnp.bfloat16)
        assert err.max() > SEEDED_L1_TOL

    def test_mass_conserved_with_a_third_isolated(self, kron17):
        g = kron17
        assert _isolated(g).size >= 0.3 * g.n
        eng = HubTailEngine.from_graph(g)
        x = jnp.asarray(np.random.default_rng(2).random(g.n, np.float32))
        # apply works in the engine's internal order
        y = eng.from_internal(eng.apply(eng.to_internal(x)))
        np.testing.assert_allclose(float(y.sum()), float(x.sum()),
                                   rtol=1e-5)
        iso = _isolated(g)
        np.testing.assert_array_equal(np.asarray(y)[iso], np.asarray(x)[iso])

    @pytest.mark.parametrize("batch", [1, 128])
    def test_auto_picks_hub_tail_at_the_bar(self, kron17, batch,
                                            monkeypatch):
        """n = 2^17 = HUB_TAIL_MIN_N: the heuristic picks the split
        without building any engine."""
        from repro.core.engine import heuristic_mode
        assert kron17.n == HUB_TAIL_MIN_N

        def no_build(*a, **k):
            raise AssertionError("heuristic_mode built an engine")
        monkeypatch.setattr(HubTailEngine, "from_graph", no_build)
        assert heuristic_mode(kron17, batch) == "hub_tail"


class TestSplitGauges:
    def test_gauges_follow_the_engine(self, kron12, monkeypatch):
        """The registry exports the split's hub edge share and panel fill,
        and zeroes both once a rebuild moves the graph off hub/tail."""
        import repro.core.engine as engine_mod
        from repro.obs.metrics import MetricsRegistry
        from repro.serve import GraphRegistry
        g = kron12
        monkeypatch.setattr(engine_mod, "HUB_TAIL_MIN_N", g.n)
        reg = GraphRegistry(engine="auto", update_mode="rebuild")
        metrics = MetricsRegistry()
        reg.bind_metrics(metrics)
        eng = reg.register("g", g).engine
        assert eng.name == "hub_tail"

        def gauge(name):
            return metrics.get(name).labels(graph="g").value
        assert gauge("graph_engine_hub_edge_share") == \
            pytest.approx(eng.hub_edge_share)
        assert gauge("graph_engine_panel_fill") == \
            pytest.approx(eng.panel_fill)
        assert gauge("graph_engine_tail_slot_fill") == \
            pytest.approx(eng.tail_slot_fill)
        hub_edges = int(g.deg[g.deg >= eng.hub_min_deg].sum())
        assert eng.hub_edge_share == pytest.approx(hub_edges / g.m)
        assert eng.panel_fill == pytest.approx(
            hub_edges / np.asarray(eng.panel_cols).size)
        assert 0.0 < eng.panel_fill <= 1.0
        # the bar moves above n: the next rebuild leaves hub/tail
        monkeypatch.setattr(engine_mod, "HUB_TAIL_MIN_N", g.n + 1)
        iso = _isolated(g)
        reg.apply_updates("g", insert=[(int(iso[0]), int(iso[1]))])
        assert reg.get("g").engine.name != "hub_tail"
        assert gauge("graph_engine_hub_edge_share") == 0.0
        assert gauge("graph_engine_panel_fill") == 0.0
        assert gauge("graph_engine_tail_slot_fill") == 0.0

    def test_properties_unknown_after_a_pytree_round_trip(self, kron12):
        eng = HubTailEngine.from_graph(kron12)
        leaves, treedef = jax.tree_util.tree_flatten(eng)
        eng2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert eng2.hub_edge_share is None and eng2.panel_fill is None
        assert eng2.tail_slot_fill is None


@pytest.fixture(scope="module")
def small_skewed():
    return chung_lu(3_000, avg_deg=16.0, seed=4)


def _one_edge():
    """40 vertices: one edge, the other 38 isolated (self-loop rows)."""
    return Graph.from_undirected_edges(40, np.array([5]), np.array([29]))


def _hub_seeds(g):
    """[n, 4] one-hot columns: the largest hub and three random
    vertices."""
    rng = np.random.default_rng(5)
    seeds = [int(np.argmax(g.deg)),
             *map(int, rng.choice(g.n, 3, replace=False))]
    p = np.zeros((g.n, 4))
    p[seeds, np.arange(4)] = 1.0
    return p


def _against_coo_and_dense(eng, g, batch):
    """(L1 to COO, L1 to the float64 direct solve) per column of
    cpaa_fixed through `eng`, uniform p at B = 1, `_hub_seeds` at B = 4."""
    from repro.core.pagerank import true_pagerank_dense
    p = np.full(g.n, 1.0 / g.n) if batch == 1 else _hub_seeds(g)
    pj = jnp.asarray(p, jnp.float32)
    pi = np.asarray(_pagerank(eng, g, p=pj), np.float64)
    coo = np.asarray(_pagerank(CooEngine(device_graph(g)), g, p=pj),
                     np.float64)
    ref = true_pagerank_dense(g, 0.85, p=p)
    return np.abs(pi - coo).sum(axis=0), np.abs(pi - ref).sum(axis=0)


# a scatter op in compiled HLO text, and its op_name
SCATTER_LINE = re.compile(r"= \S+ scatter\(.*op_name=\"([^\"]*)\"")


def _scatter_scopes(eng, batch, n):
    sched = make_schedule(0.85, 1e-6)
    p = jnp.ones((n,) if batch == 1 else (n, batch), jnp.float32)
    text = cpaa_fixed.lower(eng, jnp.asarray(sched.coeffs, jnp.float32), p,
                            rounds=sched.rounds).compile().as_text()
    return SCATTER_LINE.findall(text), text


class TestSlicedTail:
    """The tail as degree-sliced neighbour tables over the engine's
    internal order (hubs, tail by in-degree, self-loop rows)."""

    @pytest.mark.parametrize("batch", [1, 4])
    @pytest.mark.parametrize("graph", ["small_skewed", "kron12"])
    def test_matches_coo_and_dense(self, graph, batch, request):
        g = request.getfixturevalue(graph)
        eng = HubTailEngine.from_graph(g)
        assert eng.n_hubs > 0 and len(eng.tail_tables) > 1
        to_coo, to_dense = _against_coo_and_dense(eng, g, batch)
        assert np.all(to_coo <= 1e-5), to_coo
        assert np.all(to_dense <= (GLOBAL_L1_TOL if batch == 1
                                   else SEEDED_L1_TOL)), to_dense

    @pytest.mark.parametrize("split", ["all_hubs", "no_hubs", "one_edge"])
    def test_degenerate_splits_match_dense(self, split):
        """Every row a hub, every row in the tail, and a graph of
        self-loop rows around one edge: each is P at B = 1 and 4."""
        if split == "one_edge":
            g, thr = _one_edge(), None
        else:
            g = generators.powerlaw_ba(800, m_attach=4, seed=2)
            thr = 1 if split == "all_hubs" else 10**9
        eng = HubTailEngine.from_graph(g, hub_min_deg=thr)
        assert eng.n_hubs == (g.n if split == "all_hubs" else 0)
        if split == "all_hubs":
            assert eng.tail_tables == () and eng.n_copy == 0
        if split == "one_edge":
            assert [t.shape for t in eng.tail_tables] == [(1, 2)]
            assert eng.n_copy == g.n - 2
        for batch in (1, 4):
            to_coo, to_dense = _against_coo_and_dense(eng, g, batch)
            assert np.all(to_coo <= 1e-5), (batch, to_coo)
            assert np.all(to_dense <= SEEDED_L1_TOL), (batch, to_dense)

    def test_internal_order_is_a_permutation(self, kron12):
        eng = HubTailEngine.from_graph(kron12)
        perm = np.asarray(eng.perm)
        np.testing.assert_array_equal(np.sort(perm), np.arange(kron12.n))
        np.testing.assert_array_equal(np.asarray(eng.inv_perm)[perm],
                                      np.arange(kron12.n))
        rng = np.random.default_rng(6)
        for shape in ((kron12.n,), (kron12.n, 3)):
            x = jnp.asarray(rng.random(shape, np.float32))
            np.testing.assert_array_equal(
                np.asarray(eng.from_internal(eng.to_internal(x))),
                np.asarray(x))
        # hubs first by degree, then the sliced tail by degree, then the
        # self-loop rows
        deg = kron12.deg[perm]
        body = deg[:kron12.n - eng.n_copy]
        assert np.all(body[1:] <= body[:-1])
        iso = set(_isolated(kron12).tolist())
        assert set(perm[kron12.n - eng.n_copy:].tolist()) == iso

    @pytest.mark.parametrize("batch", [1, 4])
    def test_mass_conserved_in_internal_order(self, kron12, batch):
        eng = HubTailEngine.from_graph(kron12)
        shape = (kron12.n,) if batch == 1 else (kron12.n, batch)
        x = jnp.asarray(np.random.default_rng(7).random(shape, np.float32))
        y = np.asarray(eng.apply(x))
        assert y.shape == shape
        np.testing.assert_allclose(y.sum(axis=0), np.asarray(x).sum(axis=0),
                                   rtol=1e-5)
        # the self-loop rows are a copy of their own input
        tail = slice(kron12.n - eng.n_copy, kron12.n)
        np.testing.assert_array_equal(y[tail], np.asarray(x)[tail])

    @pytest.mark.parametrize("batch", [1, 8])
    def test_no_scatter_under_the_tail(self, small_skewed, batch):
        """The compiled solve scatters only in the panel reduce; the
        pattern finds COO's segment_sum, the control."""
        g = small_skewed
        scopes, text = _scatter_scopes(HubTailEngine.from_graph(g), batch,
                                       g.n)
        assert scopes and all("/hub_tail.panel/spmm.scatter/" in s
                              for s in scopes), scopes
        assert "/hub_tail.tail/spmm.gather/gather" in text
        coo, _ = _scatter_scopes(CooEngine(device_graph(g)), batch, g.n)
        assert coo and all("/spmm.scatter/" in s for s in coo)

    @pytest.mark.parametrize("graph", ["small_skewed", "kron12", "kron17"])
    def test_tail_slot_fill(self, graph, request):
        """Tail slots stay within 1.1 of the tail's edges; every edge into
        a tail row holds one slot (or is a copied self loop)."""
        g = request.getfixturevalue(graph)
        eng = HubTailEngine.from_graph(g)
        assert eng.tail_slot_fill >= 1 / 1.1
        live = sum(int((np.asarray(t) != g.n).sum())
                   for t in eng.tail_tables)
        assert live + eng.n_copy == eng.tail_edges == g.m - eng.hub_edges
        assert eng.tail_slot_fill == pytest.approx(
            eng.tail_edges / (sum(t.size for t in eng.tail_tables)
                              + eng.n_copy))
