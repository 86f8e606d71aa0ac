"""Measured engine selection: tuning store durability, winner determinism,
workload bucketing, probe caching, registry/service threading."""
import json

import numpy as np
import pytest

import jax

from repro.core.autotune import (
    TUNE_FORMAT_VERSION,
    Autotuner,
    FillProbeCache,
    TuningStore,
    WorkloadKey,
    default_tune_path,
    graph_fingerprint,
    log2_bucket,
    pick_winner,
)
from repro.core.engine import heuristic_mode
from repro.graph import generators
from repro.serve import GraphRegistry, PageRankService
from repro.serve.scheduler import SolveTimeEstimator


def small_graph():
    # n=100 < MIN_CANDIDATE_N and < 2*block: the tuner's shortlist is just
    # COO, so measurement passes in these tests stay milliseconds
    return generators.tri_mesh(10, 10)


def skewed_graph():
    return generators.powerlaw_ba(1500, 6, seed=0)


class TestTuningStore:
    def test_round_trip(self, tmp_path):
        store = TuningStore(tmp_path / "t.json")
        store.put("k1", {"engine": "coo", "us_per_iter": 12.5})
        g = small_graph()
        store.put_fill(g, 128, 0.25)
        # fresh object over the same file sees both tables
        store2 = TuningStore(tmp_path / "t.json")
        assert store2.get("k1") == {"engine": "coo", "us_per_iter": 12.5}
        assert store2.get_fill(g, 128) == 0.25
        assert store2.get_fill(g, 64) is None
        assert store2.load_error is None

    def test_missing_file_is_empty(self, tmp_path):
        store = TuningStore(tmp_path / "absent.json")
        assert store.get("k") is None
        assert store.load_error is None

    def test_truncated_file_falls_back_and_regenerates(self, tmp_path):
        path = tmp_path / "t.json"
        TuningStore(path).put("k1", {"engine": "fused"})
        raw = path.read_text()
        path.write_text(raw[: len(raw) // 2])   # crash mid-write, no tmp
        store = TuningStore(path)
        assert store.get("k1") is None
        assert store.load_error == "corrupt"
        # the next put atomically rewrites a valid file
        store.put("k2", {"engine": "coo"})
        data = json.loads(path.read_text())
        assert data["version"] == TUNE_FORMAT_VERSION
        assert TuningStore(path).get("k2") == {"engine": "coo"}

    def test_version_bump_orphans_entries(self, tmp_path):
        path = tmp_path / "t.json"
        path.write_text(json.dumps(
            {"version": TUNE_FORMAT_VERSION + 1,
             "entries": {"k1": {"engine": "coo"}}, "fill_probes": {}}))
        store = TuningStore(path)
        assert store.get("k1") is None
        assert store.load_error == "version"
        store.put("k2", {"engine": "coo"})
        assert json.loads(path.read_text())["version"] == TUNE_FORMAT_VERSION

    def test_dir_path_gets_tuning_json(self, tmp_path, monkeypatch):
        assert TuningStore(tmp_path).path == tmp_path / "tuning.json"
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "cache"))
        assert default_tune_path() == tmp_path / "cache" / "tuning.json"


class TestPickWinner:
    def test_fastest_wins_beyond_jitter(self):
        measured = {"coo": 2.0, "hub_tail": 1.0}
        assert pick_winner(measured, "coo") == "hub_tail"

    def test_heuristic_kept_within_jitter(self):
        measured = {"coo": 1.05, "hub_tail": 1.0}
        assert pick_winner(measured, "coo", jitter_tol=0.10) == "coo"
        assert pick_winner(measured, "coo", jitter_tol=0.01) == "hub_tail"

    def test_empty_measurements_fall_back(self):
        assert pick_winner({}, "fused") == "fused"   # heuristic verbatim

    def test_exact_tie_breaks_by_candidate_order_not_dict_order(self):
        a = {"fused": 1.0, "hub_tail": 1.0}
        b = {"hub_tail": 1.0, "fused": 1.0}
        # sharded heuristic measured nothing: pure argmin + order tie-break
        assert pick_winner(a, "sharded_1d", jitter_tol=0.0) == \
            pick_winner(b, "sharded_1d", jitter_tol=0.0) == "hub_tail"

    def test_same_measurements_same_winner(self):
        measured = {"coo": 3.0, "hub_tail": 2.9, "fused": 2.5}
        picks = {pick_winner(dict(measured), "coo") for _ in range(10)}
        assert picks == {"fused"}


class TestWorkloadKey:
    def test_buckets_and_str(self):
        g = small_graph()
        key = WorkloadKey.from_graph(g, batch=48, backend="cpu",
                                     device_count=1)
        assert key.n_bucket == log2_bucket(g.n)
        assert key.m_bucket == log2_bucket(g.m)
        assert key.batch == 64           # rounded up to the bucket edge
        assert key.skew_bucket == 0      # meshes have no hubs
        assert key.as_str() == (f"v{TUNE_FORMAT_VERSION}/cpu/d1/"
                                f"n{key.n_bucket}/m{key.m_bucket}/s0/b6")

    def test_same_shape_class_same_key(self):
        k1 = WorkloadKey.from_graph(generators.tri_mesh(10, 10), batch=8,
                                    backend="cpu", device_count=1)
        k2 = WorkloadKey.from_graph(generators.tri_mesh(11, 10), batch=8,
                                    backend="cpu", device_count=1)
        assert k1 == k2

    def test_skew_band_separates_powerlaw_from_mesh(self):
        km = WorkloadKey.from_graph(small_graph(), backend="cpu",
                                    device_count=1)
        kp = WorkloadKey.from_graph(skewed_graph(), backend="cpu",
                                    device_count=1)
        assert kp.skew_bucket > km.skew_bucket


class TestFillProbeCache:
    def test_fingerprint_tracks_content(self):
        g1, g2 = small_graph(), generators.tri_mesh(10, 11)
        assert graph_fingerprint(g1) == graph_fingerprint(small_graph())
        assert graph_fingerprint(g1) != graph_fingerprint(g2)

    def test_auto_mode_probes_once_per_shape(self, monkeypatch):
        import repro.core.engine as engine_mod
        g = generators.caveman(30, 64, seed=0)  # dense tiles, n >= 2*block
        calls = []
        real = engine_mod.block_fill_rate

        def counting(g_, block=128, **kw):
            calls.append(block)
            return real(g_, block=block, **kw)

        monkeypatch.setattr(engine_mod, "block_fill_rate", counting)
        cache = FillProbeCache()
        m1 = heuristic_mode(g, probe_cache=cache)
        m2 = heuristic_mode(g, probe_cache=cache)
        assert m1 == m2
        assert len(calls) == 1   # second call served from the probe cache


class TestAutotuner:
    def test_measured_entry_records_environment(self, tmp_path):
        tuner = Autotuner(TuningStore(tmp_path / "t.json"))
        g = small_graph()
        dec = tuner.tune(g, 8, graph_name="mesh")
        assert dec.source == "measured"
        assert dec.us_per_iter is not None and dec.us_per_iter > 0
        entry = tuner.store.get(dec.key)
        assert entry["engine"] == dec.mode
        assert entry["backend"] == jax.default_backend()
        assert entry["device_count"] == jax.device_count()
        assert entry["jax"] == jax.__version__
        assert entry["heuristic"] == dec.heuristic

    def test_warm_store_performs_zero_measurements(self, tmp_path):
        path = tmp_path / "t.json"
        g = small_graph()
        Autotuner(TuningStore(path)).tune(g, 8)
        tuner = Autotuner(TuningStore(path))   # restarted process
        dec = tuner.tune(g, 8)
        assert dec.source == "store_hit"
        assert tuner.measured_count() == 0
        assert tuner.decision_counts == {"store_hit": 1}

    def test_require_cached_miss_falls_back_to_heuristic(self, tmp_path):
        tuner = Autotuner(TuningStore(tmp_path / "absent.json"),
                          require_cached=True)
        g = skewed_graph()
        dec = tuner.tune(g, 8)
        assert dec.source == "fallback_heuristic"
        assert dec.mode == heuristic_mode(g, 8)
        assert tuner.measured_count() == 0

    def test_require_cached_corrupt_store_falls_back(self, tmp_path):
        path = tmp_path / "t.json"
        path.write_text("{not json")
        tuner = Autotuner(TuningStore(path), require_cached=True)
        dec = tuner.tune(small_graph(), 8)
        assert tuner.store.load_error == "corrupt"
        assert dec.source == "fallback_heuristic"

    def test_failed_measurement_pass_falls_back(self, tmp_path,
                                                monkeypatch):
        tuner = Autotuner(TuningStore(tmp_path / "t.json"))
        monkeypatch.setattr(Autotuner, "_measure_candidates",
                            lambda *a, **k: (_ for _ in ()).throw(
                                RuntimeError("boom")))
        with pytest.warns(RuntimeWarning, match="RuntimeError: boom"):
            dec = tuner.tune(small_graph(), 8)
        assert dec.source == "fallback_heuristic"
        assert dec.mode == heuristic_mode(small_graph(), 8)

    def test_failed_candidate_records_its_error(self, tmp_path,
                                                monkeypatch):
        """A candidate whose build/compile raises is disqualified with the
        exception recorded and warned about, never dropped as if slow."""
        import repro.core.autotune as at
        real = at.select_engine

        def refuse_hub_tail(g, batch=None, mode="auto", **kw):
            if mode == "hub_tail":
                raise RuntimeError("RESOURCE_EXHAUSTED: refused")
            return real(g, batch, mode, **kw)

        monkeypatch.setattr(at, "select_engine", refuse_hub_tail)
        tuner = Autotuner(TuningStore(tmp_path / "t.json"), budget_s=60.0)
        with pytest.warns(RuntimeWarning, match="'hub_tail' failed"):
            dec = tuner.tune(skewed_graph(), 8)
        entry = tuner.store.get(dec.key)
        assert dec.mode != "hub_tail"
        assert entry["skipped"]["hub_tail"] == \
            "RuntimeError: RESOURCE_EXHAUSTED: refused"
        assert "hub_tail" not in entry["candidates"]

    def test_shortlist_gates_by_size_and_devices(self):
        tuner = Autotuner.__new__(Autotuner)
        tuner.store = FillProbeCache()   # duck-typed: only fills consulted
        g = small_graph()
        key = WorkloadKey.from_graph(g, backend="cpu", device_count=1)
        assert tuner._shortlist(g, key, "coo", n_dev=1, block=128) == ["coo"]
        gs = skewed_graph()
        ks = WorkloadKey.from_graph(gs, backend="cpu", device_count=1)
        cands = tuner._shortlist(gs, ks, "coo", n_dev=1, block=128)
        assert "hub_tail" in cands and "sharded_1d" not in cands
        assert cands[0] == "coo"   # heuristic measured first
        cands8 = tuner._shortlist(gs, ks, "coo", n_dev=8, block=128)
        assert "sharded_1d" in cands8 and "sharded_2d" in cands8


class TestRegistryTunedMode:
    def test_register_records_tuned_mode_and_sticks(self, tmp_path):
        reg = GraphRegistry(engine="tuned",
                            tune_cache=tmp_path / "t.json")
        reg.register("g", small_graph())
        rg = reg.get("g")
        assert rg.tuned_mode is not None
        assert reg.tuner.decision_counts.get("measured", 0) == 1

    def test_warm_store_registry_start_zero_tuning_solves(self, tmp_path):
        path = tmp_path / "t.json"
        g = small_graph()
        GraphRegistry(engine="tuned", tune_cache=path).register("g", g)
        reg = GraphRegistry(engine="tuned", tune_cache=path)
        reg.register("g", g)
        assert reg.tuner.measured_count() == 0
        assert reg.tuner.decision_counts == {"store_hit": 1}

    def test_auto_mode_uses_process_probe_cache(self):
        from repro.core.autotune import process_probe_cache
        reg = GraphRegistry()   # auto
        assert reg._probe_cache is process_probe_cache()


class TestEstimatorSeedAndReset:
    def test_seed_provides_graph_fallback_until_first_sample(self):
        est = SolveTimeEstimator()
        est.seed("g", 0.5)
        assert est.estimate("g", 8) == 0.5
        est.observe("g", 8, 0.1)
        # a real sample replaces the seed outright, no EWMA blend with it
        assert est.estimate("g", 8) == 0.1
        assert est.estimate("g", 16) == 0.1

    def test_seed_never_overwrites_observations(self):
        est = SolveTimeEstimator()
        est.observe("g", 8, 0.2)
        est.seed("g", 9.0)
        assert est.estimate("g", 16) == 0.2

    def test_reset_single_graph(self):
        est = SolveTimeEstimator()
        est.observe("a", 8, 0.1)
        est.observe("b", 8, 0.4)
        est.reset(graph="a")
        # a falls through its cleared keys to the global EWMA; b keeps its
        # exact bucket sample
        assert est.estimate("b", 8) == 0.4
        assert est.estimate("a", 8) == est._global

    def test_reset_all_still_works(self):
        est = SolveTimeEstimator(default_s=3.0)
        est.observe("a", 8, 0.1)
        est.reset()
        assert est.estimate("a", 8) == 3.0


class TestServiceEngineSwap:
    def _service(self, tmp_path):
        reg = GraphRegistry()
        reg.register("g", skewed_graph())
        return PageRankService(reg, max_batch=8, cache_capacity=16,
                               max_top_k=4)

    def test_engine_swap_resets_estimator(self, tmp_path, monkeypatch):
        svc = self._service(tmp_path)
        svc.estimator.observe("g", 8, 123.0)
        rg = svc.registry.get("g")
        real_apply = type(svc.registry).apply_updates

        def swapping(self_reg, name, insert=(), delete=()):
            out = real_apply(self_reg, name, insert=insert, delete=delete)
            # force a different engine CLASS, as a re-tune across a shape
            # bucket would
            from repro.core.engine import select_engine
            out.engine = select_engine(out.host, mode="hub_tail")
            return out

        monkeypatch.setattr(type(svc.registry), "apply_updates", swapping)
        assert type(rg.engine).__name__ == "CooEngine"
        svc.update_graph("g", insert=[(0, 7)])
        assert type(svc.registry.get("g").engine).__name__ == "HubTailEngine"
        # stale per-(graph, bucket) and per-graph EWMAs for the old engine
        # are gone (estimate may still fall back to the cross-graph global)
        assert ("g", 8) not in svc.estimator.snapshot()
        assert "g" not in svc.estimator._by_graph
        swaps = svc.metrics.engine_swaps.labels(graph="g").value
        assert swaps == 1

    def test_no_swap_no_reset(self, tmp_path):
        svc = self._service(tmp_path)
        svc.estimator.observe("g", 8, 123.0)
        svc.update_graph("g", insert=[(0, 7)])
        assert svc.estimator.estimate("g", 8) == 123.0
        assert svc.metrics.engine_swaps.labels(graph="g").value == 0

    def test_tuned_service_seeds_estimator(self, tmp_path):
        reg = GraphRegistry(engine="tuned", tune_cache=tmp_path / "t.json")
        reg.register("g", small_graph())
        svc = PageRankService(reg, max_batch=8, cache_capacity=16,
                              max_top_k=4)
        assert svc.estimator.estimate("g", 8) > 0.0


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices — run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")
class TestTunedMultidevice:
    def test_tuned_mode_with_simulated_devices(self, tmp_path):
        tuner = Autotuner(TuningStore(tmp_path / "t.json"))
        g = skewed_graph()
        dec = tuner.tune(g, 8, graph_name="pl")
        assert dec.source == "measured"
        entry = tuner.store.get(dec.key)
        assert entry["device_count"] == jax.device_count()
        # the sharded engines were at least considered (measured or
        # skipped as infeasible), never silently absent
        seen = set(entry["candidates"]) | set(entry["skipped"])
        assert "sharded_1d" in seen
