#!/usr/bin/env python3
"""Run one cell with the profiler on and split what the trace shows by the
program's own spans and scopes (bench/program_trace.py).

    python3 bench/program_split.py --workload mesh-1m.bulk --seed 7 \
        --seconds 51 [--keep DIR]

The cell runs as `bench/run.py --trace 1` runs it (same loop, window and
checks). Then one `[bench]` line on stderr gives the device's idle time
under each `bench.*` span, split by the `serve.*` stage span the host was
in, and one JSON line on stdout gives:

  correct, window_s, ticks, rounds   as the run saw them
  program_gaps                       bench span -> stage span -> idle s
  scoped_ms_per_round                scope -> device ms of the solve
                                     program per solver round
  program_ms_per_round               the solve program's device ms a round
  stage_ms_per_tick                  serve_stage_seconds by stage, host ms
                                     per window tick
  numbers                            h2d_mb (serve_h2d_bytes_total over
                                     serve_ticks_total, in the window),
                                     fence_idle_ms (idle under serve.fence
                                     per window tick),
                                     tick_idle_unattributed (% of the idle
                                     inside bench.tick under no serve.*
                                     span), gather_ms and scatter_ms (the
                                     scoped times a round)

Numbers the cell cannot give (no tick, no scope stat in the trace) are
left out. `--keep` keeps the trace under DIR. Like bench/run.py it runs
only on a TPU and exits 2 elsewhere.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402
from pathlib import Path                                   # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def _h2d_counted():
    """bench/service.py's window counters, with serve_h2d_bytes_total
    added where the service has it."""
    from bench import service
    base = service.counters

    def counters(svc):
        out = base(svc)
        h2d = getattr(svc.metrics, "h2d_bytes", None)
        if h2d is not None:
            out["h2d_bytes"] = h2d.total()
        return out

    service.counters = counters
    try:
        yield
    finally:
        service.counters = base


def numbers(run, ps) -> dict:
    """The per-layer numbers this split gives (module docstring)."""
    out = {}
    ticks = ps.spans.get("bench.tick", 0)
    tick_gaps = ps.program_gaps.get("tick", {})
    if run.counters.get("h2d_bytes") and run.counters.get("ticks"):
        out["h2d_mb"] = run.counters["h2d_bytes"] / run.counters["ticks"] \
            / 1e6
    if ticks and tick_gaps:
        out["fence_idle_ms"] = tick_gaps.get("serve.fence", 0.0) / ticks \
            * 1e-6
        out["tick_idle_unattributed"] = 100.0 * tick_gaps.get(
            "unattributed", 0.0) / sum(tick_gaps.values())
    rounds = sum(c["rounds"] for c in run.calls)
    for scope, key in (("spmm.gather", "gather_ms"),
                       ("spmm.scatter", "scatter_ms")):
        t = ps.scoped_s(scope, run.program)
        if rounds and t > 0:
            out[key] = 1000.0 * t / rounds
    return out


def main(argv=None, root: Path = ROOT, require_tpu: bool = True,
         t0: float | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    t0 = T_PROCESS0 if t0 is None else t0
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    if require_tpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax
    platform = jax.devices()[0].platform
    if require_tpu and platform != "tpu":
        print(f"program_split: no TPU ({platform}); nothing was run",
              file=sys.stderr)
        return 2
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        enable_compile_cache()
    from bench import drive, manifest, program_trace, trace
    cell = manifest.resolve(root, args.workload)
    is_op = trace.SELECTORS[platform][0]
    with contextlib.ExitStack() as stack:
        tdir = args.keep or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="bench-split-"))
        with _h2d_counted():
            run = drive.run_cell(cell, args.seed & ((1 << 64) - 1),
                                 args.seconds, tdir, t0)
        ps = program_trace.summarize(program_trace.load_events(
            trace.find_xplane(tdir), is_op), is_op)
    drive.log(f"program gaps: {ps.table()}")
    rounds = sum(c["rounds"] for c in run.calls)
    per_round = (lambda s: 1000.0 * s / rounds) if rounds else (lambda s: 0.0)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": jax.devices()[0].device_kind, "correct": run.correct,
        "checks": {k: list(v) for k, v in run.checks.items()},
        "window_s": run.trace.window_s, "busy_s": run.trace.busy_s,
        "ticks": ps.spans.get("bench.tick", 0), "rounds": rounds,
        "program_gaps": {b: {k: v * 1e-9 for k, v in d.items()}
                         for b, d in ps.program_gaps.items()},
        "scoped_ms_per_round": {
            s: per_round(ps.scoped_s(s, run.program))
            for s in program_trace.SCOPES},
        "program_ms_per_round": per_round(run.trace.module_s(run.program)),
        "stage_ms_per_tick": {
            k[len("stage."):]: 1000.0 * v / run.counters["ticks"]
            for k, v in run.counters.items()
            if k.startswith("stage.") and run.counters.get("ticks")},
        "numbers": numbers(run, ps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
