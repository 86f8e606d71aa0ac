"""Resolve a cell by name from BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, metric or cell
is a file of its own, found by its name:

  configs   the path in the manifest's `configs[].file` (JSON: graph
            generator and sizes, service settings, source, assumed)
  graphs    bench/graphs/<generator>.py, named by the configuration's
            `graph.generator`: `make(seed, **sizes)` -> (n, u, v)
  traffic   bench/traffic/<traffic>.json (the loop and its parameters)
  loops     bench/loops/<loop>.py, named by the traffic's `loop`:
            `drive(ctx)`, `check(run, cell, ref, answers)` and
            `control(cell, ref, low, seed)`
  metrics   bench/metrics/<metric>.py, a module with `read(run)` that
            returns a number, or None where it finds nothing to read; a
            metric split by the end-to-end metric it moves
            (`device_idle.serve`) falls back to the reader of its stem
            (`device_idle.py`)
  limits    bench/limits/<workload>.json (each number compared for
            `correct`, and its limit)

Nothing here lists cells, configurations, generators, loops or metrics.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path


@dataclass
class Cell:
    root: Path
    name: str
    entry: dict           # the workloads[] entry
    config: dict          # the configuration file's content
    traffic: dict         # the traffic file's content
    limits: dict          # number compared -> limit
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # metric entries this cell reports with --trace 1

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def load_manifest(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: Path, workload: str) -> Cell:
    root = Path(root)
    man = load_manifest(root)
    entries = {w["name"]: w for w in man["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}"
                       f"; known: {sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in man["configs"]}
    cfg = json.loads((root / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())
    return Cell(root=root, name=workload, entry=entry, config=cfg,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in man["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in man["per_layer"]
                           if _reports(m, workload)])


def module(root: Path, kind: str, name: str):
    """bench/<kind>/<name>.py, loaded from its path (a name may hold dots
    and dashes, and `global` is a keyword, so these are not packages)."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} module {name!r} at {path}")
    tag = "".join(ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, metric: str):
    """The `read` function of bench/metrics/<metric>.py, or of the metric's
    stem (the name before its first dot) where it has no file of its own."""
    base = Path(root) / "bench" / "metrics"
    name = metric if (base / f"{metric}.py").is_file() else \
        metric.split(".")[0]
    return module(root, "metrics", name).read
