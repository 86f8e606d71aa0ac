"""Sharded-engine comparison: 1D vs 2D vs single-device COO across device
counts.

The device count is locked at jax init, so `sharded_compare` (called from
`benchmarks.run`) spawns one subprocess per device count with
`XLA_FLAGS=--xla_force_host_platform_device_count=N`; each subprocess times
the FULL `cpaa_fixed` solve per engine (partition build excluded — it is a
per-epoch host cost, not a per-solve cost) and prints one JSON line that the
parent collects.

On CPU the "mesh" is N slices of one socket, so the sharded engines pay real
collective overhead with no extra FLOPs behind it — the section tracks the
relative trajectory of that overhead run over run (and the 1D vs 2D
collective-volume gap), not an absolute speedup; the speedup column crosses
1 only on real multi-chip meshes.

Standalone:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.sharded_bench --quick
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _records_for_this_process(quick: bool, batches) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_schedule
    from repro.core.engine import (CooEngine, Sharded1DEngine,
                                   Sharded2DEngine, factor_grid)
    from repro.core.pagerank import cpaa_fixed
    from repro.graph import generators
    from repro.graph.ops import device_graph

    rounds = 12
    reps = 2 if quick else 3
    n_dev = jax.device_count()
    sched = make_schedule(0.85, rounds=rounds)
    coeffs = jnp.asarray(sched.coeffs, jnp.float32)
    g = generators.tri_mesh(60, 60) if quick else generators.tri_mesh(140, 140)
    lane = 8 if quick else 32

    engines = [("coo", CooEngine(device_graph(g))),
               ("sharded_1d", Sharded1DEngine.from_graph(g, lane=lane))]
    if n_dev >= 4:
        engines.append(("sharded_2d",
                        Sharded2DEngine.from_graph(g, grid=factor_grid(n_dev),
                                                   lane=lane)))

    def timed(eng, p):
        """Min over reps (noise-robust; matches engine_bench._time_solve)."""
        pi, _ = cpaa_fixed(eng, coeffs, p, rounds=rounds)  # compile + warm
        jax.block_until_ready(pi)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            pi, _ = cpaa_fixed(eng, coeffs, p, rounds=rounds)
            jax.block_until_ready(pi)
            best = min(best, time.perf_counter() - t0)
        return best

    records = []
    for bt in batches:
        key = jax.random.PRNGKey(0)
        p = jnp.abs(jax.random.normal(
            key, (g.n,) if bt == 1 else (g.n, bt), jnp.float32))
        t_coo = None
        for name, eng in engines:
            dt = timed(eng, p)
            if name == "coo":
                t_coo = dt
            records.append({"n_dev": n_dev, "family": "mesh", "n": g.n,
                            "m": g.m, "B": bt, "engine": name,
                            "rounds": rounds,
                            "us_per_solve": round(dt * 1e6, 1),
                            "speedup_vs_coo": round(t_coo / dt, 3)})
    return records


def sharded_compare(quick: bool = False, device_counts=None):
    """Returns (csv_rows, json_records); spawns one subprocess per count.

    CPU only: each child simulates its mesh with fake host devices. On an
    accelerator the calling process already holds the chip, and a child
    that needs it fails or hangs, so any other backend is refused. A child
    that fails fails the comparison."""
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"sharded_compare simulates meshes in CPU subprocesses; on the "
            f"{jax.default_backend()!r} backend this process holds the "
            f"device its children would need. Run the sharded engines on "
            f"real chips with `chip_smoke.py --four-chips` instead.")
    if device_counts is None:
        device_counts = (8,) if quick else (2, 4, 8)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = ""  # overwritten per count below
    env["PYTHONPATH"] = (os.path.join(here, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    records = []
    for n_dev in device_counts:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        cmd = [sys.executable, "-m", "benchmarks.sharded_bench", "--emit-json"]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              cwd=here, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(f"sharded_bench subprocess ({n_dev} devices) "
                               f"failed with exit code {proc.returncode}:\n"
                               f"{proc.stderr}")
        records.extend(json.loads(proc.stdout.strip().splitlines()[-1]))
    rows = [("n_dev", "family", "n", "m", "B", "engine", "us_per_solve",
             "speedup_vs_coo")]
    for r in records:
        rows.append((r["n_dev"], r["family"], r["n"], r["m"], r["B"],
                     r["engine"], r["us_per_solve"], r["speedup_vs_coo"]))
    return rows, records


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--emit-json", action="store_true",
                    help="print records as one JSON line (subprocess mode)")
    args = ap.parse_args(argv)
    batches = (8,) if args.quick else (1, 128)
    records = _records_for_this_process(args.quick, batches)
    if args.emit_json:
        print(json.dumps(records))
    else:
        for r in records:
            print(",".join(str(r[k]) for k in
                           ("n_dev", "family", "B", "engine", "us_per_solve",
                            "speedup_vs_coo")))


if __name__ == "__main__":
    main()
