#!/usr/bin/env python3
"""The chip benchmark: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell's configuration, traffic and metric files by name
(bench/manifest.py), builds the system from the seed, warms every shape the
window uses, measures for --seconds, compares what the window produced with
the float64 reference, and prints one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 the per-layer ones, read from the profiler's trace of the window
and the program's counters. The numbers compared for `correct` are printed
beside their limits as the last lines of stderr and under "checks".

It runs only on a TPU with at least the chips the cell asks for: anything
else exits 2 and prints no result. JAX's persistent compilation cache is
kept in <checkout>/.jax_cache.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse                                            # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402
import traceback                                           # noqa: E402
from pathlib import Path                                   # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ".jax_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _num(x: float):
    """A JSON number, or a string for what JSON cannot hold (inf, nan)."""
    return x if x == x and abs(x) != float("inf") else repr(x)


def main(argv=None, root: Path = ROOT, require_tpu: bool = True,
         t0: float | None = None) -> int:
    """Run one cell. `require_tpu=False` skips the chip check and the
    compile cache (tests drive the whole run on the CPU this way)."""
    args = parse(argv)
    t0 = T_PROCESS0 if t0 is None else t0
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import manifest
    try:
        cell = manifest.resolve(root, args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if require_tpu:
        # the cache lives in the checkout, at a fixed path
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / CACHE_DIR)
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if require_tpu and (platform != "tpu" or len(devices) < cell.chips):
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {platform} device(s); nothing was run",
              file=sys.stderr)
        return 2
    try:
        import repro
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    if require_tpu:
        if not all(Path(d).resolve().is_relative_to(root / "src")
                   for d in repro.__path__):
            print("bench: repro was not imported from this checkout",
                  file=sys.stderr)
            return 2
        from repro.launch.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        enable_compile_cache()
    from bench import drive
    from bench.peaks import peaks
    try:
        peak = peaks(kind) if require_tpu else {}
        seed = args.seed & ((1 << 64) - 1)
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            run = drive.run_cell(cell, seed, args.seconds,
                                 tdir if args.trace else None, t0)
        run.peak = peak
        metrics = {}
        for m in (cell.per_layer if args.trace else cell.end_to_end):
            value = manifest.reader(root, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    drive.log(f"compiles inside the window: {run.compiles_in_window}")
    for name, (value, limit) in run.checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    result["checks"] = {name: {"value": _num(value), "limit": _num(limit)}
                        for name, (value, limit) in run.checks.items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
