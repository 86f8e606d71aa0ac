"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, emit roofline JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch pna --shape molecule
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
      --out experiments/dryrun.jsonl

The FIRST TWO LINES below must run before any other import: jax locks the
device count on first init, and the dry-run needs 512 placeholder CPU
devices to build the production mesh. (Smoke tests / benches never import
this module, so they keep their 1-device view.)
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import all_cells, get               # noqa: E402
from repro.launch.analysis import collective_bytes, roofline_terms  # noqa: E402
from repro.launch.mesh import make_production_mesh     # noqa: E402


def _attach_shardings(args_tree, specs_tree, mesh):
    """Zip PartitionSpecs onto ShapeDtypeStructs as NamedShardings."""
    from jax.sharding import NamedSharding

    def attach(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    out = []
    for args, specs in zip(args_tree, specs_tree):
        is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
        out.append(jax.tree.map(attach, args, specs,
                                is_leaf=lambda x: is_spec(x)))
    return tuple(out)


def _compile_plan(plan, mesh):
    step = plan.step
    if step is None:  # shard_map paths need the mesh (cpaa-pagerank)
        step = plan.static["step_builder"](mesh)
    sharded_args = _attach_shardings(plan.abstract_args, plan.in_specs, mesh)
    with jax.set_mesh(mesh):
        lowered = step.lower(*sharded_args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    return compiled, cost, collective_bytes(hlo)


def _cost_vector(cost, coll):
    vec = {"flops": float(cost.get("flops", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    vec.update({k: float(v) for k, v in coll.items()})
    return vec


def _extrapolate(plan, mesh, cost, coll, verbose):
    """Correct count-loops-once costs: cost(L,M) = a + M*b + M*L*c, solved
    from reduced-depth probe compiles (see DryRunPlan.cost_model)."""
    cm = plan.cost_model
    if not cm:
        return _cost_vector(cost, coll), False
    L, M = cm["L"], cm["M"]
    if L <= 2 and M == 1:
        return _cost_vector(cost, coll), False
    _, c11, k11 = _compile_plan(cm["probe"](1, 1), mesh)
    f11 = _cost_vector(c11, k11)
    _, c21, k21 = _compile_plan(cm["probe"](2, 1), mesh)
    f21 = _cost_vector(c21, k21)
    if M > 1:
        _, c12, k12 = _compile_plan(cm["probe"](1, 2), mesh)
        f12 = _cost_vector(c12, k12)
    else:
        f12 = None
    out = {}
    for key in f11:
        c = f21[key] - f11[key]
        b = (f12[key] - f11[key] - c) if f12 else 0.0
        a = f11[key] - b - c
        val = a + M * b + M * L * c
        out[key] = max(val, 0.0)
    if verbose:
        print(f"  cost extrapolated from probes (L={L}, M={M}): "
              f"flops/dev {f11['flops']:.3g} -> {out['flops']:.3g}",
              flush=True)
    return out, True


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True):
    mod = get(arch)
    cell = next(c for c in mod.cells() if c.shape == shape)
    if cell.skip_reason:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": cell.skip_reason}
    t0 = time.time()
    plan = mod.build(shape, multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t_lower = time.time() - t0
    compiled, cost, coll = _compile_plan(plan, mesh)
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    corrected, extrapolated = _extrapolate(plan, mesh, cost, coll, verbose)
    cost = {"flops": corrected["flops"],
            "bytes accessed": corrected["bytes_accessed"]}
    coll = {k: corrected.get(k, v) for k, v in coll.items()}
    roof = roofline_terms(cost, coll, chips, plan.model_flops)
    rec = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "status": "ok", "chips": chips,
        "kind": cell.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
        },
        "cost": {"flops": cost.get("flops", 0.0),
                 "bytes_accessed": cost.get("bytes accessed", 0.0)},
        "collectives": coll,
        "roofline": roof.to_dict(),
        "note": plan.note,
    }
    if verbose:
        mb = rec["memory"]["peak_per_device"] / 2**20
        print(f"[{arch} x {shape} | {'2-pod' if multi_pod else '1-pod'}] "
              f"OK compile={t_compile:.0f}s peak/dev={mb:.0f}MiB "
              f"dominant={roof.dominant} "
              f"terms(ms)=C{roof.compute_s*1e3:.1f}/M{roof.memory_s*1e3:.1f}"
              f"/N{roof.collective_s*1e3:.1f}", flush=True)
        print(f"  memory_analysis: {mem}", flush=True)
    return rec


# Cheapest cells first so partial runs produce useful coverage.
_COST_ORDER = {
    "pna": 0, "meshgraphnet": 1, "dlrm-rm2": 2, "dimenet": 3, "graphcast": 4,
    "cpaa-pagerank": 5, "h2o-danube-1.8b": 6, "deepseek-7b": 7,
    "granite-moe-3b-a800m": 8, "qwen2.5-32b": 9, "qwen3-moe-235b-a22b": 10,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args(argv)

    if args.all:
        cells = sorted(all_cells(),
                       key=lambda ac: (_COST_ORDER.get(ac[0], 99), ac[1].shape))
        jobs = []
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            jobs += [(a, c.shape, mp) for a, c in cells]
    else:
        jobs = [(args.arch, args.shape, args.multi_pod)]
        if args.both_meshes:
            jobs.append((args.arch, args.shape, True))

    done = set()
    if args.out and args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skipped"):
                        done.add((r["arch"], r["shape"], r["multi_pod"]))
                except json.JSONDecodeError:
                    pass

    n_fail = 0
    out_f = open(args.out, "a") if args.out else None
    for arch, shape, mp in jobs:
        if (arch, shape, mp) in done:
            continue
        try:
            rec = run_cell(arch, shape, mp)
        except Exception as e:  # noqa: BLE001 - record and continue
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "error", "error": repr(e)[:500]}
            n_fail += 1
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
            out_f.flush()
    if out_f:
        out_f.close()
    print(f"dry-run finished, failures: {n_fail}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
