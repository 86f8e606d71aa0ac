"""Unit tests for the CI benchmark-regression gate
(benchmarks/check_regression.py): threshold math, median normalization,
the jitter floor, the [bench-skip] escape hatch, and one-sided entries."""
import json
import pathlib
import subprocess
import sys

SCRIPT = str(pathlib.Path(__file__).parent.parent / "benchmarks"
             / "check_regression.py")

BASE = {("mesh", 1, "coo"): 50000.0,
        ("mesh", 1, "block_ell_fused"): 20000.0,
        ("kmer", 128, "coo"): 30000.0}


def _payload(entries):
    return {"engine_compare": [
        {"family": f, "B": b, "engine": e, "us_per_solve": us}
        for (f, b, e), us in entries.items()]}


def _run(tmp_path, old, new, *extra, msg="routine commit"):
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(_payload(old)))
    pn.write_text(json.dumps(_payload(new)))
    return subprocess.run(
        [sys.executable, SCRIPT, "--old", str(po), "--new", str(pn),
         "--commit-msg", msg, *extra],
        capture_output=True, text=True, timeout=60)


def test_uniform_machine_shift_passes(tmp_path):
    """A 1.5x-slower machine must not trip the gate: the median ratio
    normalizes it away."""
    new = {k: v * 1.5 for k, v in BASE.items()}
    r = _run(tmp_path, BASE, new)
    assert r.returncode == 0, r.stdout
    assert "OK" in r.stdout


def test_single_entry_regression_fails(tmp_path):
    new = dict(BASE)
    new[("mesh", 1, "block_ell_fused")] *= 2.0
    r = _run(tmp_path, BASE, new)
    assert r.returncode == 1, r.stdout
    assert "FAIL" in r.stdout and "block_ell_fused" in r.stdout


def test_bench_skip_marker_bypasses(tmp_path):
    new = {k: v * 3.0 for k, v in BASE.items()}
    new[("mesh", 1, "coo")] *= 4.0
    r = _run(tmp_path, BASE, new, msg="slower but correct [bench-skip]")
    assert r.returncode == 0, r.stdout
    assert "[bench-skip]" in r.stdout


def test_jitter_floor_entries_never_fail(tmp_path):
    """Entries faster than --min-us are too noisy to gate: informational."""
    old = dict(BASE)
    old[("tiny", 1, "coo")] = 3000.0
    new = dict(old)
    new[("tiny", 1, "coo")] = 9000.0      # 3x, but below the 8000us floor
    r = _run(tmp_path, old, new)
    assert r.returncode == 0, r.stdout
    assert "info" in r.stdout


def test_one_sided_entries_ignored(tmp_path):
    new = dict(BASE)
    del new[("kmer", 128, "coo")]
    new[("new_family", 8, "coo")] = 1000.0
    r = _run(tmp_path, BASE, new)
    assert r.returncode == 0, r.stdout
    assert r.stdout.count("note:") == 2


def test_raw_mode_catches_uniform_slowdown(tmp_path):
    new = {k: v * 1.5 for k, v in BASE.items()}
    r = _run(tmp_path, BASE, new, "--normalize", "none")
    assert r.returncode == 1, r.stdout


def test_adaptive_compare_entries_are_gated(tmp_path):
    """adaptive_compare records join the gate keyed (family, B,
    engine/mode) — disjoint from engine_compare keys by construction."""
    def payload(slow: float):
        return {
            "engine_compare": [{"family": "mesh", "B": 1, "engine": "coo",
                                "us_per_solve": 50000.0}],
            "adaptive_compare": [
                {"family": "mesh", "B": 1, "engine": "coo", "mode": "fixed",
                 "us_per_solve": 40000.0},
                {"family": "mesh", "B": 1, "engine": "coo",
                 "mode": "adaptive", "us_per_solve": 20000.0 * slow},
            ],
        }
    import json
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps(payload(1.0)))
    pn.write_text(json.dumps(payload(2.0)))   # adaptive entry regressed 2x
    import subprocess, sys
    r = subprocess.run([sys.executable, SCRIPT, "--old", str(po), "--new",
                        str(pn), "--commit-msg", "routine"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    assert "coo/adaptive" in r.stdout


def test_update_churn_entries_gate_with_their_own_floor(tmp_path):
    """update_churn records join the gate keyed (family, batch_edges,
    update-engine/mode) and use the LOWER --min-us-update jitter floor:
    the incremental apply path sits well under the solve floor but must
    still gate."""
    def payload(slow: float):
        return {
            "engine_compare": [{"family": "mesh", "B": 1, "engine": "coo",
                                "us_per_solve": 50000.0}],
            "update_churn": [
                {"family": "community", "B": 32, "engine": "coo",
                 "mode": "rebuild", "us_per_update": 15000.0},
                {"family": "community", "B": 32, "engine": "coo",
                 "mode": "incremental", "us_per_update": 3500.0 * slow},
            ],
        }
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps(payload(1.0)))
    pn.write_text(json.dumps(payload(3.0)))  # incremental regressed 3x
    r = subprocess.run([sys.executable, SCRIPT, "--old", str(po), "--new",
                        str(pn), "--commit-msg", "routine"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    assert "update-coo/incremental" in r.stdout
    # ...but a sub-floor entry (below 1000us baseline) stays informational
    def tiny(slow: float):
        p = payload(1.0)
        p["update_churn"].append(
            {"family": "community", "B": 1, "engine": "coo",
             "mode": "incremental", "us_per_update": 400.0 * slow})
        return p
    po.write_text(json.dumps(tiny(1.0)))
    pn.write_text(json.dumps(tiny(3.0)))
    r = subprocess.run([sys.executable, SCRIPT, "--old", str(po), "--new",
                        str(pn), "--commit-msg", "routine"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout
    assert "info" in r.stdout


def test_backend_mismatch_refuses_to_compare(tmp_path):
    """A baseline stamped with a different meta.backend than the fresh run
    must refuse (exit 2) instead of normalizing cross-backend ratios; files
    without a meta stamp (all the fixtures above) keep comparing."""
    def payload(backend):
        p = _payload(BASE)
        if backend is not None:
            p["meta"] = {"backend": backend, "device_count": 1}
        return p
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps(payload("cpu")))
    pn.write_text(json.dumps(payload("tpu")))
    r = subprocess.run([sys.executable, SCRIPT, "--old", str(po), "--new",
                        str(pn), "--commit-msg", "routine"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 2, r.stdout
    assert "backend mismatch" in r.stdout
    # one-sided stamp (old baseline predates meta) -> still compares
    po.write_text(json.dumps(payload(None)))
    r = subprocess.run([sys.executable, SCRIPT, "--old", str(po), "--new",
                        str(pn), "--commit-msg", "routine"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout
