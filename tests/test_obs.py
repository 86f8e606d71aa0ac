"""Observability layer: metric primitives, traces, convergence telemetry,
exposition, and the serve-path wiring (exactly-once dispositions, span
model, Formula 8 bound, drain overrun policies)."""
import json
import urllib.request
import warnings

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.engine import apply_counts, reset_apply_counts
from repro.graph import generators
from repro.obs.convergence import (ConvergenceLog, TickTelemetry,
                                   UpdateTelemetry)
from repro.obs.export import (MetricsServer, SNAPSHOT_SCHEMA, snapshot,
                              to_prometheus, validate_snapshot)
from repro.obs.metrics import (Histogram, MetricsRegistry, NULL_REGISTRY)
from repro.obs.trace import NULL_TRACE, Trace, Tracer, profiled
from repro.serve import (GraphRegistry, PageRankService, PPRQuery,
                         ServeMetrics)


def make_service(g, **kw):
    registry = GraphRegistry()
    registry.register("g", g)
    defaults = dict(max_batch=8, cache_capacity=64, max_top_k=8)
    defaults.update(kw)
    return PageRankService(registry, **defaults)


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

class TestMetricsPrimitives:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help")
        c.inc()
        c.inc(2.5)
        assert c.total() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        gge = reg.gauge("t_depth", "help")
        gge.set(5)
        gge.inc(2)
        gge.dec()
        assert gge.total() == 6.0

    def test_histogram_quantiles_within_gamma_bound(self):
        """DDSketch guarantee: the reported quantile is within half a bucket
        (factor sqrt(gamma), ~1% at gamma=1.02) of the SAMPLE at the target
        rank — that sample, not an interpolated quantile, is the reference."""
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=-6.0, sigma=2.0, size=5000)
        h = Histogram()
        for v in samples:
            h.observe(float(v))
        ordered = np.sort(samples)
        for q in (0.5, 0.9, 0.99, 0.999):
            rank = int(np.ceil(q * (len(samples) - 1) + 1))
            exact = float(ordered[rank - 1])
            approx = h.quantile(q)
            assert abs(approx - exact) / exact < 0.0101, (q, exact, approx)
        assert h.count == 5000
        assert np.isclose(h.sum, samples.sum())
        assert h.min == samples.min() and h.max == samples.max()
        np.testing.assert_allclose(h.mean, samples.mean())

    def test_histogram_zero_bucket_and_empty(self):
        h = Histogram()
        assert h.quantile(0.5) == 0.0          # empty -> 0.0
        h.observe(0.0)
        h.observe(-1e-9)                       # clock-resolution roundoff
        assert h.count == 2
        assert h.quantile(0.99) <= 0.0
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_histogram_quantile_clamped_to_observed_range(self):
        h = Histogram()
        h.observe(1.0)
        assert h.quantile(0.0) == 1.0
        assert h.quantile(1.0) == 1.0

    def test_histogram_merge_equals_union(self):
        rng = np.random.default_rng(1)
        a, b = rng.exponential(1.0, 400), rng.exponential(5.0, 600)
        ha, hb, hu = Histogram(), Histogram(), Histogram()
        for v in a:
            ha.observe(float(v))
            hu.observe(float(v))
        for v in b:
            hb.observe(float(v))
            hu.observe(float(v))
        ha.merge(hb)
        assert ha.count == hu.count
        assert ha.quantile(0.99) == hu.quantile(0.99)
        with pytest.raises(ValueError):
            ha.merge(Histogram(gamma=1.1))

    def test_family_label_validation(self):
        reg = MetricsRegistry()
        fam = reg.counter("t_served", "help", ("graph", "disposition"))
        fam.labels(graph="g", disposition="solved").inc()
        with pytest.raises(ValueError):
            fam.labels(graph="g")              # missing label
        with pytest.raises(ValueError):
            fam.labels(graph="g", disposition="solved", extra="x")
        with pytest.raises(ValueError):
            fam.inc()                          # labeled family needs .labels
        assert fam.total() == 1.0

    def test_family_children_sorted_and_cached(self):
        reg = MetricsRegistry()
        fam = reg.counter("t_c", "", ("graph",))
        fam.labels(graph="b").inc(2)
        fam.labels(graph="a").inc(1)
        assert fam.labels(graph="b") is fam.labels(graph="b")
        assert [v for v, _ in fam.children()] == [("a",), ("b",)]

    def test_registry_idempotent_and_conflicting_registration(self):
        reg = MetricsRegistry()
        a = reg.counter("t_x", "help", ("graph",))
        assert reg.counter("t_x", "other help", ("graph",)) is a
        with pytest.raises(ValueError):
            reg.gauge("t_x", "", ("graph",))       # kind conflict
        with pytest.raises(ValueError):
            reg.counter("t_x", "", ("other",))     # label conflict

    def test_registry_reset_keeps_families(self):
        reg = MetricsRegistry()
        c = reg.counter("t_y", "")
        h = reg.histogram("t_h", "")
        c.inc(3)
        h.observe(1.0)
        reg.reset()
        assert reg.get("t_y") is c
        assert c.total() == 0.0 and h.merged().count == 0

    def test_null_registry_absorbs_everything(self):
        c = NULL_REGISTRY.counter("t_n", "", ("graph",))
        c.labels(graph="g").inc()
        c.inc()
        h = NULL_REGISTRY.histogram("t_nh", "")
        h.observe(1.0)
        assert c.total() == 0.0
        assert h.quantile(0.99) == 0.0
        assert h.percentiles() == (0.0, 0.0, 0.0)
        assert h.merged().count == 0
        assert c.children() == ()


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

class TestTrace:
    def test_span_lifecycle_and_kinds(self):
        tr = Trace("query", qid=1)
        tr.mark("submit")
        tr.begin("queue")
        assert tr.end("queue") >= 0.0
        with tr.span("solve_device", kind="device"):
            pass
        assert tr.span_names() == ["submit", "queue", "solve_device"]
        kinds = {s.name: s.kind for s in tr.spans}
        assert kinds["solve_device"] == "device"
        assert tr.end("never_begun") == 0.0     # no-op, not an error
        d = tr.as_dict()
        assert d["meta"] == {"qid": 1}
        assert all(s["duration_s"] >= 0.0 for s in d["spans"])

    def test_tracer_bounded_retention(self):
        tracer = Tracer(keep=4)
        for i in range(10):
            tr = tracer.start("query", qid=i)
            tr.mark("submit")
            tracer.finish(tr)
        assert len(tracer.finished) == 4
        assert tracer.last().meta["qid"] == 9

    def test_disabled_tracer_hands_out_null(self):
        tracer = Tracer(enabled=False)
        tr = tracer.start("query")
        assert tr is NULL_TRACE
        tr.begin("queue")
        tr.mark("submit")
        tracer.finish(tr)
        assert len(tracer.finished) == 0
        assert NULL_TRACE.spans == []           # recorded nothing

    def test_profiled_noop_without_logdir(self):
        with profiled(None):
            pass                                # must be a free no-op

    def test_profiled_raises_when_profiler_cannot_start(self, monkeypatch,
                                                        tmp_path):
        import jax

        def broken(logdir):
            raise RuntimeError("profiler unavailable")

        monkeypatch.setattr(jax.profiler, "trace", broken)
        with pytest.raises(RuntimeError, match="profiler unavailable"):
            with profiled(str(tmp_path)):
                pass


# ---------------------------------------------------------------------------
# convergence telemetry
# ---------------------------------------------------------------------------

def _tick(i, used, bound, **kw):
    defaults = dict(tick=i, graph="g", engine="CooEngine", bucket=8,
                    columns=4, rounds_used=used, rounds_bound=bound,
                    residual=1e-5, converged_frac=1.0, tol=1e-4, c=0.85)
    defaults.update(kw)
    return TickTelemetry(**defaults)


class TestConvergenceLog:
    def test_totals_survive_ring_eviction(self):
        log = ConvergenceLog(keep=4)
        for i in range(20):
            log.record_tick(_tick(i, used=6, bound=12))
        assert len(log.ticks) == 4
        s = log.summary()
        assert s["ticks_recorded"] == 20
        assert s["rounds_used_total"] == 120
        assert s["rounds_saved_ratio"] == pytest.approx(0.5)
        assert s["bound_violations"] == 0

    def test_bound_violation_detected(self):
        log = ConvergenceLog()
        log.record_tick(_tick(0, used=13, bound=12))
        assert log.bound_violations == 1
        assert not log.ticks[0].within_bound

    def test_update_retention(self):
        log = ConvergenceLog()
        log.record_update(UpdateTelemetry(
            graph="g", kind="incremental", edges_changed=4, cache_dropped=1,
            cache_retained=3, duration_s=0.01))
        assert log.updates[0].retention == pytest.approx(0.75)
        assert log.summary()["cache_retention"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

def _sample_registry():
    reg = MetricsRegistry()
    reg.counter("t_served_total", "served", ("graph",)).labels(
        graph="mesh").inc(3)
    h = reg.histogram("t_latency_seconds", "latency", ("graph",))
    for v in (0.001, 0.002, 0.004, 0.008):
        h.labels(graph="mesh").observe(v)
    reg.gauge("t_depth", "queue depth").set(2)
    return reg


class TestExposition:
    def test_prometheus_text_format(self):
        text = to_prometheus(_sample_registry())
        assert "# TYPE t_served_total counter" in text
        assert 't_served_total{graph="mesh"} 3' in text
        assert "# TYPE t_latency_seconds histogram" in text
        assert 't_latency_seconds_count{graph="mesh"} 4' in text
        assert 'le="+Inf"} 4' in text
        assert "t_depth 2" in text
        # cumulative le counts never decrease
        cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                if line.startswith("t_latency_seconds_bucket")]
        assert cums == sorted(cums)

    def test_snapshot_valid_and_quantiles_monotone(self):
        snap = snapshot(_sample_registry(), meta={"elapsed_s": 1.0})
        assert snap["schema"] == SNAPSHOT_SCHEMA
        assert validate_snapshot(snap) == []
        s = snap["metrics"]["t_latency_seconds"]["series"][0]
        assert s["min"] <= s["p50"] <= s["p99"] <= s["p999"] <= s["max"]
        json.dumps(snap)                        # JSON-ready end to end

    def test_validator_rejects_broken_snapshots(self):
        assert validate_snapshot([]) != []
        assert any("schema" in e for e in validate_snapshot(
            {"schema": "bogus", "metrics": {}}))
        snap = snapshot(_sample_registry())
        snap["metrics"]["t_served_total"]["series"][0]["value"] = -1
        assert any("negative counter" in e for e in validate_snapshot(snap))
        snap2 = snapshot(_sample_registry())
        snap2["metrics"]["t_latency_seconds"]["series"][0]["p99"] = 1e9
        assert any("monotone" in e for e in validate_snapshot(snap2))

    def test_validator_rejects_bound_violations(self):
        log = ConvergenceLog()
        log.record_tick(_tick(0, used=13, bound=12))
        snap = snapshot(_sample_registry(), convergence=log)
        assert any("bound_violations" in e for e in validate_snapshot(snap))

    def test_http_endpoint_serves_both_formats(self):
        server = MetricsServer(_sample_registry(), port=0).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            text = urllib.request.urlopen(f"{base}/metrics",
                                          timeout=10).read().decode()
            assert 't_served_total{graph="mesh"} 3' in text
            snap = json.loads(urllib.request.urlopen(
                f"{base}/metrics.json", timeout=10).read())
            assert validate_snapshot(snap) == []
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# serve-path wiring
# ---------------------------------------------------------------------------

class TestServeInstrumentation:
    def test_single_query_traced_end_to_end(self):
        """Acceptance: one non-cached query yields the full span model,
        with the device span fenced (kind='device')."""
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(3, 7)))
        svc.run_until_drained()
        tr = svc.metrics.tracer.last("query")
        names = tr.span_names()
        for name in ("submit", "queue", "batch_form", "solve_dispatch",
                     "solve_device", "materialize"):
            assert name in names, f"missing span {name}"
        assert len(names) >= 5
        kinds = {s.name: s.kind for s in tr.spans}
        assert kinds["solve_device"] == "device"
        assert all(s.closed for s in tr.spans)
        # the trace survives into the snapshot export
        snap = svc.metrics.snapshot()
        assert any(
            {"solve_device", "materialize"} <=
            {sp["name"] for sp in t["spans"]} for t in snap["traces"])

    def test_latency_and_stage_histograms_populated(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        for i in range(4):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        svc.run_until_drained()
        lat = svc.metrics.latency.labels(graph="g", disposition="solved")
        assert lat.count == 4
        assert lat.quantile(0.99) >= lat.quantile(0.5) > 0.0
        for stage in ("batch_form", "solve_dispatch", "solve_device",
                      "materialize"):
            assert svc.metrics.stage.labels(stage=stage).count == 1
        assert svc.metrics.stage.labels(stage="queue").count == 4

    def test_rounds_bound_never_exceeded_adaptive(self):
        """Acceptance: Formula 8 stays a hard cap under adaptive serving."""
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, adaptive=True)
        for i in range(6):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i, i + 11),
                                tol=1e-3))
        svc.run_until_drained()
        st_ = svc.stats
        assert st_["rounds_used"] <= st_["rounds_bound"]
        log = svc.metrics.convergence
        assert log.bound_violations == 0
        assert all(t.within_bound for t in log.ticks)
        assert all(0.0 <= t.converged_frac <= 1.0 for t in log.ticks)
        snap = svc.metrics.snapshot()
        assert validate_snapshot(snap) == []

    def test_stats_backcompat_dict(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(1,)))
        svc.run_until_drained()
        svc.submit(PPRQuery(qid=1, graph="g", seeds=(1,)))   # cache hit
        st_ = svc.stats
        for key in ("queries", "cache_hits", "solves", "solved_queries",
                    "dropped_queries", "ticks", "padded_columns", "updates",
                    "rounds_used", "rounds_bound", "noop_updates",
                    "incremental_updates", "cache_dropped", "cache_retained",
                    "refreshes"):
            assert key in st_, key
        assert st_["queries"] == 2
        assert st_["cache_hits"] == 1
        assert st_["solved_queries"] == 1

    def test_detail_false_keeps_counters_only(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, metrics=ServeMetrics(detail=False))
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(2,)))
        svc.run_until_drained()
        assert svc.stats["solved_queries"] == 1      # counters still live
        assert svc.metrics.latency.labels(
            graph="g", disposition="solved").count == 0
        assert len(svc.metrics.tracer.finished) == 0

    def test_registry_gauges_and_update_timings(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, invalidation_radius=2)
        reg = svc.metrics.registry
        assert reg.get("graph_epoch").labels(graph="g").value == 0
        # g.m counts the symmetrized directed list; the gauge publishes
        # undirected edges
        assert reg.get("graph_edges").labels(graph="g").value == g.m // 2
        # "g" was built before bind_metrics, so only post-bind builds are
        # timed: register a second graph through the live registry
        svc.registry.register("h", generators.tri_mesh(5, 6))
        assert reg.get("registry_build_seconds").labels(graph="h").count == 1
        engines = reg.get("graph_engine_info")
        live = [v for v, inst in engines.children() if inst.value == 1.0]
        assert ("g",) in [v[:1] for v in live]
        svc.update_graph("g", insert=[(0, g.n - 1)])
        assert reg.get("graph_epoch").labels(graph="g").value == 1
        upd = reg.get("registry_update_seconds")
        assert sum(inst.count for _, inst in upd.children()) == 1


class TestExactlyOnceAccounting:
    def test_cache_hit_counted_once_not_twice(self):
        """Satellite (a): a submit-time hit and its tick-time twin fill are
        each ONE disposition — cache hits+misses equals queries answered."""
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=1)
        # two identical in-flight queries in different tick groups: the
        # first solves, the second is twin-filled from the cache at tick
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(5, 9)))
        svc.submit(PPRQuery(qid=1, graph="g", seeds=(5, 9)))
        results = svc.run_until_drained()
        assert results[1].cached
        # a third identical query hits synchronously at submit
        assert svc.submit(PPRQuery(qid=2, graph="g", seeds=(5, 9))) is not None
        st_ = svc.stats
        assert st_["queries"] == 3
        assert st_["cache_hits"] == 2
        assert st_["solved_queries"] == 1
        assert st_["queries"] == (st_["cache_hits"] + st_["solved_queries"]
                                  + st_["dropped_queries"])
        cs = svc.cache.stats()
        assert cs["hits"] == 2 and cs["misses"] == 1
        assert cs["hits"] + cs["misses"] == st_["queries"]

    def test_in_flight_twins_share_column_but_count_individually(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=8)
        for i in range(4):                     # 4 queries, 2 distinct keys
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i % 2,)))
        results = svc.run_until_drained()
        st_ = svc.stats
        assert st_["solves"] == 1
        assert st_["solved_queries"] == 4      # every query counted
        assert results[0].batch_size == 2      # but only 2 solved columns
        assert svc.cache.stats()["misses"] == 4


class TestDrainOverrun:
    def test_overrun_raises_by_default(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=1)
        for i in range(3):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        with pytest.raises(RuntimeError, match="did not drain"):
            svc.run_until_drained(max_ticks=1)

    def test_drain_in_exactly_max_ticks_is_not_overrun(self):
        """Regression: 3 queries at max_batch=1 drain in exactly 3 ticks —
        the boundary case must not raise."""
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=1)
        for i in range(3):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        results = svc.run_until_drained(max_ticks=3)
        assert len(results) == 3

    def test_overrun_drop_counts_and_warns(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=1)
        for i in range(3):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i,)))
        with pytest.warns(RuntimeWarning, match="dropped 2"):
            results = svc.run_until_drained(max_ticks=1, on_overrun="drop")
        assert len(results) == 1               # only the drained query
        st_ = svc.stats
        assert st_["dropped_queries"] == 2
        assert st_["queries"] == (st_["cache_hits"] + st_["solved_queries"]
                                  + st_["dropped_queries"])
        assert svc.pending() == 0

    def test_invalid_overrun_policy_rejected(self):
        g = generators.tri_mesh(9, 11)
        svc = make_service(g)
        with pytest.raises(ValueError):
            svc.run_until_drained(on_overrun="ignore")


class TestRetraceDetector:
    def test_steady_state_ticks_do_not_retrace(self):
        """`apply_counts` counts trace-time engine applies: repeated
        same-bucket ticks must reuse the compiled solve."""
        g = generators.tri_mesh(9, 11)
        svc = make_service(g, max_batch=4)
        svc.submit(PPRQuery(qid=0, graph="g", seeds=(0,)))
        svc.run_until_drained()                # compile the 1-bucket
        reset_apply_counts()
        for i in range(1, 4):
            svc.submit(PPRQuery(qid=i, graph="g", seeds=(i + 3,)))
            svc.run_until_drained()
        assert sum(apply_counts().values()) == 0, apply_counts()


# ---------------------------------------------------------------------------
# property test: disposition conservation across random interleavings
# ---------------------------------------------------------------------------

def _run_interleaving(ops, seed):
    """Drive a service through a random op sequence and check the
    conservation invariant after every step."""
    g = generators.tri_mesh(6, 7)
    svc = make_service(g, max_batch=2, cache_capacity=32,
                       invalidation_radius=2, refresh_batch=2, adaptive=True)
    rng = np.random.default_rng(seed)
    qid = 0

    def check():
        st_ = svc.stats
        disposed = (st_["cache_hits"] + st_["solved_queries"]
                    + st_["dropped_queries"])
        assert st_["queries"] == disposed + svc.pending()
        assert st_["rounds_used"] <= st_["rounds_bound"]

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for op in ops:
            if op == 0:        # submit (small seed pool -> hits + twins)
                s = (int(rng.integers(0, 6)),)
                svc.submit(PPRQuery(qid=qid, graph="g", seeds=s, tol=1e-3))
                qid += 1
            elif op == 1:      # edge update (may be a duplicate no-op)
                u, v = int(rng.integers(0, g.n)), int(rng.integers(0, g.n))
                if u != v:
                    svc.update_graph("g", insert=[(u, v)])
            elif op == 2:
                svc.tick()
            elif op == 3:
                svc.refresh_tick()
            else:              # drop-mode drain with a tiny tick budget
                svc.run_until_drained(max_ticks=1, on_overrun="drop")
            check()
        svc.run_until_drained(max_ticks=100, on_overrun="drop")
    check()
    assert svc.pending() == 0
    st_ = svc.stats
    assert st_["queries"] == (st_["cache_hits"] + st_["solved_queries"]
                              + st_["dropped_queries"])
    assert svc.metrics.convergence.bound_violations == 0


class TestDispositionConservation:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_seeded_interleavings(self, seed):
        rng = np.random.default_rng(100 + seed)
        ops = rng.integers(0, 5, size=25).tolist()
        _run_interleaving(ops, seed)

    @settings(max_examples=15, deadline=None)
    @given(ops=st.lists(st.integers(min_value=0, max_value=4),
                        min_size=1, max_size=25),
           seed=st.integers(min_value=0, max_value=2**16))
    def test_random_interleavings(self, ops, seed):
        _run_interleaving(ops, seed)
