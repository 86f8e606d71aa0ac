"""The harness on the CPU at a tiny size: cells resolve by name from a
manifest, every cell runs end to end through the three loops with every
metric reader called in process, a cell (with a new traffic mix, or a new
graph generator and a new loop) can be added with new files and entries
alone, the copied arrival generators are seeded and hit their rates, and
the command refuses to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from bench import drive, manifest, service  # noqa: E402
from tiny import run_cell  # noqa: E402

CELLS = tiny.CELLS
BIG_SEED = 2**33 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_cells_resolve_by_name(root):
    man = manifest.load_manifest(root)
    for w in man["workloads"]:
        cell = manifest.resolve(root, w["name"])
        assert cell.config["name"] == w["config"]
        loop = manifest.module(root, "loops", cell.traffic["loop"])
        assert callable(loop.drive) and callable(loop.check)
        generator = cell.config["graph"]["generator"]
        assert callable(manifest.module(root, "graphs", generator).make)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.reader(root, m["name"]))
    with pytest.raises(KeyError):
        manifest.resolve(root, "no-such.cell")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(root, workload, trace):
    rc, res = run_cell(root, workload, trace)
    assert rc == 0 and res is not None
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    cell = manifest.resolve(root, workload)
    if trace:
        # CPU: no peaks, so no roofline; the rest is read
        want = {m["name"] for m in cell.per_layer
                if not m["name"].startswith("spmm_roofline")}
        assert set(res["metrics"]) == want
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in res["metrics"].values():
        assert np.isfinite(m["value"])


def test_same_seed_same_inputs(root):
    cell = manifest.resolve(root, "graph500-19.global")
    a = drive.make_graph(root, cell.config["graph"], BIG_SEED)
    b = drive.make_graph(root, cell.config["graph"], BIG_SEED)
    c = drive.make_graph(root, cell.config["graph"], BIG_SEED + 1)
    assert a[0] == b[0] and np.array_equal(a[1], b[1]) and \
        np.array_equal(a[2], b[2])
    assert not np.array_equal(a[1], c[1])


def test_a_cell_is_added_with_new_files_and_entries_alone(root, tmp_path):
    new = tiny.make_root(tmp_path)
    (new / "bench" / "traffic" / "bulk-zipf.json").write_text(json.dumps(
        {"loop": "closed", "queued_min": 64, "seeds_per_query": [1, 2],
         "seed_popularity": {"zipf": 1.1}, "top_k": 8, "check_sample": 4}))
    (new / "bench" / "limits" / "mesh-1m.bulk-zipf.json").write_text(
        json.dumps({"topk_err": 3e-4}))
    (new / "bench" / "metrics" / "answered.bulk-zipf.py").write_text(
        "def read(run):\n    return run.answered\n")
    man = json.loads((new / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "mesh-1m.bulk-zipf", "config": "mesh-1m",
                             "traffic": "bulk-zipf", "chips": 1,
                             "why": "popular seeds"})
    for m in man["end_to_end"]:
        if m["name"] == "ppr_qps":
            m["workloads"].append("mesh-1m.bulk-zipf")
    man["per_layer"].append({"name": "answered.bulk-zipf", "unit": "queries",
                             "better": "higher", "source": "host_clock",
                             "layer": "service", "moves": "ppr_qps",
                             "workloads": ["mesh-1m.bulk-zipf"]})
    (new / "BENCHMARK.json").write_text(json.dumps(man))
    rc, res = run_cell(new, "mesh-1m.bulk-zipf", trace=0)
    assert rc == 0 and res["correct"]
    assert set(res["metrics"]) == {"setup_s", "ppr_qps"}
    rc, res = run_cell(new, "mesh-1m.bulk-zipf", trace=1)
    assert rc == 0 and res["metrics"]["answered.bulk-zipf"]["value"] > 0


RING = '''"""A ring with random chords."""
import numpy as np


def make(seed, n, chords):
    rng = np.random.default_rng(seed)
    u = np.arange(n)
    a, b = rng.integers(0, n, (2, chords))
    return n, np.concatenate([u, a]), np.concatenate([(u + 1) % n, b])
'''

BURST = '''"""Every query at once: `burst` queued, ticked until answered."""
import time

from bench import service


def drive(ctx):
    run, traffic = ctx.run, ctx.cell.traffic
    svc, rng, query, tick, results = service.service_parts(
        run, ctx.cell.config, traffic, ctx.g)
    sets = service.seed_sets(traffic, ctx.g.n, traffic["burst"], rng)
    for q, s in enumerate(sets):
        svc.submit(query(q, s))
    ctx.profiler.start()
    run.setup_s = time.perf_counter() - ctx.t_process0
    before = service.counters(svc)
    ctx.open_window()
    t0 = time.perf_counter()
    while svc.pending():
        tick(record=True)
    run.window_s = time.perf_counter() - t0
    ctx.close_window()
    ctx.profiler.stop()
    run.counters = service.delta(before, service.counters(svc))
    run.answered = run.attempted = len(sets)
    queries = dict(enumerate(sets))
    return queries, {q: results[q] for q in queries}


check = service.check_topk
control = service.control_topk
'''


def test_a_generator_and_a_loop_are_added_with_files_alone(tmp_path):
    new = tiny.make_root(tmp_path)
    bench = new / "bench"
    (bench / "graphs" / "ring.py").write_text(RING)
    (bench / "loops" / "burst.py").write_text(BURST)
    cfg = json.loads((bench / "configs" / "mesh-1m.json").read_text())
    cfg.update(name="ring-2k", graph={"generator": "ring", "n": 2000,
                                      "chords": 3000})
    (bench / "configs" / "ring-2k.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "burst", "burst": 48, "seeds_per_query": [1, 3],
         "top_k": 8, "check_sample": 6}))
    (bench / "limits" / "ring-2k.burst.json").write_text(
        json.dumps({"topk_err": 3e-4}))
    man = json.loads((new / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "ring-2k", "source": "a ring",
                           "file": "bench/configs/ring-2k.json",
                           "reduced": [], "why": "a new generator"})
    man["workloads"].append({"name": "ring-2k.burst", "config": "ring-2k",
                             "traffic": "burst", "chips": 1,
                             "why": "a new loop"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("ppr_qps", "host_tick_ms.bulk"):
            m["workloads"].append("ring-2k.burst")
    (new / "BENCHMARK.json").write_text(json.dumps(man))
    rc, res = run_cell(new, "ring-2k.burst", trace=0)
    assert rc == 0 and res["correct"] and res["attempted"] == 48
    assert set(res["metrics"]) == {"setup_s", "ppr_qps"}
    rc, res = run_cell(new, "ring-2k.burst", trace=1)
    assert rc == 0 and res["correct"]
    assert set(res["metrics"]) == {"host_tick_ms.bulk"}
    from bench import control
    cell = manifest.resolve(new, "ring-2k.burst")
    assert control.control_numbers(cell, 5)["topk_err"] > 3e-4


def test_arrivals_are_seeded_and_hit_their_rate():
    def draw(seed, kind="poisson"):
        return service.arrivals({"rate_qps": 50.0, "arrivals": kind}, 200.0,
                              np.random.default_rng([seed, 1]))
    a, b, c = draw(BIG_SEED), draw(BIG_SEED), draw(BIG_SEED + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    for t in (a, draw(7, "bursty")):
        assert np.all(np.diff(t) >= 0) and t[-1] < 200.0
        # 10,000 expected; a Poisson count's sd is 100
        assert abs(t.size / 200.0 - 50.0) < 2.5
    sets = service.seed_sets({"seeds_per_query": [1, 3]}, 1000, 500,
                           np.random.default_rng(1))
    assert {len(s) for s in sets} == {1, 2, 3}
    assert sets == service.seed_sets({"seeds_per_query": [1, 3]}, 1000, 500,
                                   np.random.default_rng(1))


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr
