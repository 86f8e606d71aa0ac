"""A tiny copy of the benchmark for CPU tests: the real manifest, metric
readers and cells, with every configuration cut to a few thousand vertices
and the open loop slowed to what XLA:CPU sustains.

`pending.json` holds the manifest entries and limits of cells whose files
are in bench/ but which wait for their chip measurements before they join
BENCHMARK.json (PERF.md, Open questions); the tiny copy runs them too."""
from __future__ import annotations

import io
import json
import shutil
import sys
import time
from contextlib import redirect_stdout
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
PENDING = json.loads((Path(__file__).with_name("pending.json")).read_text())
CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]
         + PENDING["workloads"]]

TINY_GRAPHS = {
    "mesh-1m": {"generator": "tri_mesh", "rows": 40, "cols": 30},
    "graph500-19": {"generator": "kronecker", "scale": 11, "edgefactor": 16,
                    "A": 0.57, "B": 0.19, "C": 0.19},
}


def make_root(tmp: Path, serve_rate: float = 40.0) -> Path:
    """A checkout-shaped directory under `tmp` whose cells resolve to tiny
    graphs; limits and traffic otherwise as committed."""
    root = Path(tmp)
    for sub in ("metrics", "traffic", "limits", "configs", "graphs",
                "loops"):
        shutil.copytree(REPO / "bench" / sub, root / "bench" / sub)
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    man["configs"] += PENDING["configs"]
    man["workloads"] += PENDING["workloads"]
    for key in ("end_to_end", "per_layer"):
        known = {m["name"]: m for m in man[key]}
        for m in PENDING[key]:
            if m["name"] in known:
                known[m["name"]]["workloads"] += m["workloads"]
            else:
                man[key].append(m)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for cell, limits in PENDING["limits"].items():
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(limits))
    for name, graph in TINY_GRAPHS.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["graph"] = graph
        path.write_text(json.dumps(cfg))
    serve = root / "bench" / "traffic" / "serve.json"
    t = json.loads(serve.read_text())
    t.update(rate_qps=serve_rate, prefill_s=1.0, first_tick_queue=8,
             tail_s=5.0, warm_buckets=[8])
    serve.write_text(json.dumps(t))
    return root


def run_cell(root, workload, trace=0, seed=2**33 + 12345, seconds=1.5):
    """One in-process run with the chip check skipped; (exit code, the
    parsed last line of stdout or None)."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from bench import run as bench_run
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", str(trace)],
                            root=root, require_tpu=False,
                            t0=time.perf_counter())
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
