"""Benchmark regression gate for CI.

Compares the fresh `engine_compare`, `adaptive_compare`, `update_churn`,
`scale_compare`, `serve_pagerank` AND `load_bench` records of a
`benchmarks.run --json` output against the committed baseline
(BENCH_pagerank.json) and fails when any entry — keyed (family, B, engine)
for engine_compare, (family, B, "engine/mode") for adaptive_compare,
(family, batch_edges, "update/mode") for update_churn (per-batch update
latency, so update-path regressions gate like solve regressions),
(family, B, "scale-engine/weight_dtype") for the paper-scale
per-iteration times, (family, B, "serve/mean" | "serve/p99") for the
serving section (the p99 key gates TAIL latency, which a mean can hide),
and (family, B, "load-tenant/sched" |
"goodput-tenant/sched") for the open-loop scheduling section (per-tenant
p99 under bursty load, plus goodput-under-SLO inverted to
us-per-good-query so lower is better) — slowed down by more than
--threshold.

Benchmark numbers only compare within one backend: when BOTH files carry a
`meta.backend` stamp and they differ (a cpu baseline against a tpu run),
the gate REFUSES to compare — exit 2, with instructions to regenerate the
baseline — instead of silently normalizing a cross-backend ratio into
nonsense.

CI runners and dev machines differ in absolute speed, so by default each
entry's new/old time ratio is normalized by the MEDIAN ratio across all
entries before the threshold is applied: a uniform machine-speed shift
cancels out, and only entries that regressed relative to the rest of the
suite trip the gate (--normalize none compares raw ratios). Entries present
on only one side are reported but never fail the gate — families and
engines come and go — and entries whose baseline time sits below --min-us
are jitter-dominated and only informational.

Escape hatch: a `[bench-skip]` marker in the commit message (or whatever is
passed via --commit-msg; CI passes the PR title for pull requests) skips the
check entirely — for commits that knowingly trade speed for correctness.

    PYTHONPATH=src python -m benchmarks.run --quick --json BENCH_new.json
    python benchmarks/check_regression.py \
        --old BENCH_pagerank.json --new BENCH_new.json
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import subprocess
import sys

SKIP_MARKER = "[bench-skip]"


def _load_payload(path: str) -> tuple[dict, dict[tuple, float]]:
    """(meta, entries) of one benchmark JSON; meta may be empty (old files
    and the test fixtures carry none — the backend refusal only applies
    when both sides are stamped)."""
    with open(path) as f:
        payload = json.load(f)
    meta = payload.get("meta") or {}
    out = {}
    for rec in payload.get("engine_compare", []):
        out[(rec["family"], rec["B"], rec["engine"])] = rec["us_per_solve"]
    for rec in payload.get("adaptive_compare", []):
        # "engine/mode" keeps these keys disjoint from engine_compare's
        out[(rec["family"], rec["B"],
             f"{rec['engine']}/{rec['mode']}")] = rec["us_per_solve"]
    for rec in payload.get("update_churn", []):
        # per-batch update latency; B is the batch's edge count here
        out[(rec["family"], rec["B"],
             f"update-{rec['engine']}/{rec['mode']}")] = rec["us_per_update"]
    for rec in payload.get("scale_compare", []):
        if rec.get("us_per_iter") is None:
            continue   # probed-and-skipped formats (block-ELL at scale)
        # paper-scale per-iteration times; "scale-" prefixed so the keys
        # stay disjoint and pick up their own jitter floor
        out[(rec["family"], rec["B"],
             f"scale-{rec['engine']}/{rec['weight_dtype']}")] = \
            rec["us_per_iter"]
    for rec in payload.get("serve_pagerank", []):
        if rec.get("family") != "serve_pagerank":
            continue   # the serve_overhead record is informational only
        # the serve section gates on the TAIL, not just the mean: a p99
        # regression with a flat mean is exactly the failure mode the
        # observability layer exists to catch
        out[(rec["family"], rec["B"], "serve/mean")] = rec["us_per_query"]
        out[(rec["family"], rec["B"], "serve/p99")] = rec["p99_us"]
    for rec in payload.get("load_bench", []):
        # open-loop scheduling: per-(tenant, scheduler) tail latency and
        # goodput-under-SLO. Goodput (higher-better qps) is inverted to
        # us-per-good-query so one lower-is-better threshold gates
        # everything; a zero-goodput run simply drops the key (reported as
        # one-sided, never a silent pass)
        tag = f"{rec['tenant']}/{rec['scheduler']}"
        if not math.isnan(rec["p99_us"]):
            out[(rec["family"], rec["B"], f"load-{tag}")] = rec["p99_us"]
        if rec.get("goodput_qps", 0.0) > 0.0:
            out[(rec["family"], rec["B"], f"goodput-{tag}")] = \
                1e6 / rec["goodput_qps"]
    return meta, out


def _commit_message() -> str:
    try:
        return subprocess.run(["git", "log", "-1", "--format=%B"],
                              capture_output=True, text=True,
                              timeout=30).stdout
    except (OSError, subprocess.SubprocessError):
        return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--old", required=True, help="committed baseline JSON")
    ap.add_argument("--new", required=True, help="fresh benchmark JSON")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="max tolerated fractional slowdown (default 0.25)")
    ap.add_argument("--normalize", choices=("median", "none"),
                    default="median",
                    help="divide each ratio by the suite-wide median ratio "
                         "(cancels machine-speed differences; default)")
    ap.add_argument("--min-us", type=float, default=8000.0,
                    help="entries whose baseline time is below this are "
                         "jitter-dominated: reported but never failed "
                         "(default 8000us)")
    ap.add_argument("--min-us-update", type=float, default=1000.0,
                    help="jitter floor for update_churn entries (default "
                         "1000us): per-batch update latency is steadier "
                         "than micro-solves AND the fast (incremental) "
                         "path sits well under the solve floor — without "
                         "its own floor the tentpole path would never "
                         "gate")
    ap.add_argument("--min-us-serve", type=float, default=1000.0,
                    help="jitter floor for serve_pagerank entries (default "
                         "1000us): per-query latency at large B amortizes "
                         "to well under the solve floor, and p99 on a "
                         "quick run rests on few samples")
    ap.add_argument("--commit-msg", default=None,
                    help="text to scan for the [bench-skip] marker "
                         "(default: git log -1)")
    args = ap.parse_args(argv)

    msg = args.commit_msg if args.commit_msg is not None else _commit_message()
    if SKIP_MARKER in msg:
        print(f"{SKIP_MARKER} found in commit message — skipping the "
              f"benchmark regression gate")
        return 0

    old_meta, old = _load_payload(args.old)
    new_meta, new = _load_payload(args.new)
    ob, nb = old_meta.get("backend"), new_meta.get("backend")
    if ob is not None and nb is not None and ob != nb:
        print(f"backend mismatch: baseline {args.old} was measured on "
              f"{ob!r}, fresh {args.new} on {nb!r} — benchmark times only "
              f"compare within one backend. Regenerate the baseline on "
              f"{nb!r} (benchmarks.run --json) instead of gating across "
              f"backends.")
        return 2
    shared = sorted(set(old) & set(new))
    if not shared:
        print(f"no shared engine_compare entries between {args.old} and "
              f"{args.new}; nothing to gate")
        return 0
    for key in sorted(set(old) ^ set(new)):
        side = "baseline only" if key in old else "fresh only"
        print(f"note: entry {key} is {side}; ignored")

    ratios = {k: new[k] / old[k] for k in shared}
    norm = statistics.median(ratios.values()) if args.normalize == "median" \
        else 1.0
    print(f"{len(shared)} entries; median new/old ratio {norm:.3f} "
          f"(normalize={args.normalize}, threshold +{args.threshold:.0%})")

    failures = []
    for key in shared:
        rel = ratios[key] / norm
        if key[2].startswith("update"):
            floor = args.min_us_update
        elif key[2].startswith(("serve", "load-", "goodput-")):
            floor = args.min_us_serve
        else:
            floor = args.min_us
        if rel <= 1.0 + args.threshold:
            status = "ok"
        elif old[key] < floor:
            status = "info"   # too fast to time reliably; never gates
        else:
            status = "FAIL"
        print(f"  {status:4s} {key[0]:<12s} B={key[1]:<4d} {key[2]:<16s} "
              f"{old[key]:>10.1f} -> {new[key]:>10.1f} us  "
              f"(x{ratios[key]:.2f}, normalized x{rel:.2f})")
        if status == "FAIL":
            failures.append(key)

    if failures:
        print(f"\nbenchmark regression: {len(failures)} entries slowed "
              f"down >{args.threshold:.0%} vs {args.old}: {failures}\n"
              f"(commit with {SKIP_MARKER} in the message to bypass)")
        return 1
    print("benchmark regression gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
