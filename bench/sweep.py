#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one graph build, then
the cell's loop at each rate in turn, one JSON line per rate.

    python3 bench/sweep.py --workload <open-loop cell> --seed 1 \
        --seconds 20 --rates 30,40,50,60

The cell has to be in BENCHMARK.json already (its rate is what the sweep
overrides).

A rate is sustained where the queue at the window's last tick is no
longer than at its first few; the cell's rate is then set by hand in its
traffic file at about 0.8 of the highest such rate. The answers are not
checked here (the cell's own runs check them).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import drive, manifest
    from repro.graph.structure import Graph
    cell = manifest.resolve(ROOT, args.workload)
    loop = manifest.module(ROOT, "loops", cell.traffic["loop"])
    n, u, v = drive.make_graph(ROOT, cell.config["graph"], args.seed)
    g = Graph.from_undirected_edges(n, u, v)
    for rate in (float(r) for r in args.rates.split(",")):
        run = drive.Run(seed=args.seed)
        at_rate = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_qps=rate))
        loop.drive(drive.Context(
            run=run, cell=at_rate, g=g, seconds=args.seconds,
            profiler=drive.Profiler(None), compiles=drive.compile_counter(),
            t_process0=time.perf_counter()))
        gc.collect()
        lat = np.asarray(run.latencies)
        ticks = run.calls
        print(json.dumps({
            "rate_qps": rate, "attempted": run.attempted,
            "answered": run.answered,
            "p50_s": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_s": float(np.percentile(lat, 95)) if lat.size else None,
            "depth": run.depth,
            "tick_s": [t["end"] - t["start"] for t in ticks],
            "columns": [t["columns"] for t in ticks],
            "buckets": [t["bucket"] for t in ticks],
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
