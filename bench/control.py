#!/usr/bin/env python3
"""The control of a cell's `correct`: the reference put in the program's
place and computed one precision step lower (bfloat16 for the float32 the
configurations state), judged by the same comparison as a run (the cell's
loop's `control`). Every number it reads has to exceed the cell's limit.

    python3 bench/control.py --workload mesh-1m.global --seeds 1,2,3

One JSON line per seed: {"workload", "seed", "numbers": {name: value},
"limits": {name: limit}}. It runs on the host alone (numpy and scipy), at
the cell's own size; the queries are drawn from the seed as a run draws
them.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_numbers(cell, seed: int) -> dict:
    from bench import drive, manifest
    from bench.reference import Reference
    n, u, v = drive.make_graph(cell.root, cell.config["graph"], seed)
    loop = manifest.module(cell.root, "loops", cell.traffic["loop"])
    return loop.control(cell, Reference(n, u, v),
                        Reference(n, u, v, dtype="bfloat16"), seed)


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import manifest
    cell = manifest.resolve(root, args.workload)
    for s in args.seeds.split(","):
        seed = int(s) & ((1 << 64) - 1)
        nums = control_numbers(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          "numbers": nums,
                          "limits": {k: cell.limits[k] for k in nums}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
