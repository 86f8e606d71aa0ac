#!/usr/bin/env python3
"""Chip smoke test: the PPR service and the CPAA solve at 10^6 vertices.

    python chip_smoke.py                # one TPU chip: the whole main path
    python chip_smoke.py --four-chips   # four chips: sharded-1d / sharded-2d
                                        # against one-chip COO, nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]
        # the same control flow on tiny graphs (Pallas in interpret mode);
        # never prints the ok line

Everything runs in this one process (a chip belongs to one process) and
goes through the entry points a user calls: `pagerank_serve.make_service`
(GraphRegistry -> select_engine -> PageRankService), `select_engine` and
`cpaa`. One-chip phases:

  1. graphs from a seed: mesh-1m = paper_dataset("NACA0015", 10.0)
     (1.04e6 vertices, NACA0015's published size) and chunglu-1m =
     scale_dataset("chunglu-1m") (1e6 vertices, ~1.3e7 directed edges);
  2. the pagerank-serve operating point (c=0.85, tol=1e-4, max_batch=128,
     max_top_k=32, adaptive solves): both graphs under engine="auto", then
     mesh-1m under engine="fused" (both Pallas kernels). Each service takes
     256 queries of 1-3 seeds, two edge-update batches, ~10% repeats that
     should hit the cache, and 8 fresh queries on the updated graph;
  3. one global PageRank solve (`cpaa`) on chunglu-1m through the engine
     `select_engine` picks;
  4. every checked answer against a float64 scipy reference built here
     from the edge set (x <- c P x + (1 - c) p to 1e-12), within the bounds
     below; the fused solve must contain `tpu_custom_call`.

Compile seconds, fenced per-tick seconds and peak device bytes are printed
for information only: they are not a benchmark. The last stdout line is
{"ok": true, "device": {...}} only when every check passed on a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from dataclasses import replace
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

SEED = 0
C, TOL = 0.85, 1e-4              # the pagerank-serve operating point
GLOBAL_TOL = 1e-6                # cpaa's default tolerance
MAX_BATCH, MAX_TOP_K = 128, 32
REF_TOL = 1e-12                  # float64 reference: L1 step size at exit

# Error bounds. f32 rounding: every round applies P once and updates the
# recurrence, a few f32 operations per entry plus the in-degree sum; 32
# ulps of relative L1 error per round covers that with room for the
# summation order, and the geometric coefficients keep the total within
# rounds * (per-round error).
EPS32 = float(np.finfo(np.float32).eps)


def f32_slack(rounds: int) -> float:
    return rounds * 32 * EPS32


def ppr_score_bound(rounds_cap: int) -> float:
    # adaptive exit: chunk residual <= tol, plus a tail <= tol / 2
    # (default_chunk sizes the chunk for that), and the final column
    # normalization at most doubles an L1 error: 3 tol. One entry's error
    # is at most the vector's L1 error.
    return 3 * TOL + f32_slack(rounds_cap)


def global_l1_bound(rounds: int) -> float:
    # fixed rounds from Formula 8: relative truncation error < tol, at most
    # doubled by the normalization
    return 2 * GLOBAL_TOL + f32_slack(rounds)


class Failures(list):
    def check(self, ok: bool, what: str) -> bool:
        print(("PASS " if ok else "FAIL ") + what, flush=True)
        if not ok:
            self.append(what)
        return ok


# ---------------------------------------------------------------- graphs --

def undirected_keys(g) -> np.ndarray:
    lo = np.minimum(g.src, g.dst).astype(np.int64)
    hi = np.maximum(g.src, g.dst).astype(np.int64)
    keep = lo < hi
    return np.unique(lo[keep] * g.n + hi[keep])


def churn_batches(g, keys, rng, n_batches: int, size: int):
    """Edge-update batches of triadic-closure churn: each deletes `size`
    random edges and inserts `size` two-hop shortcuts u-v-w -> u-w (the
    local change of a refined mesh, or a friend-of-friend link)."""
    n = g.n
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(g.dst, minlength=n), out=ptr[1:])
    nbr = g.src[np.argsort(g.dst, kind="stable")]
    deg = np.diff(ptr)
    out = []
    for _ in range(n_batches):
        u = rng.integers(0, n, 8 * size)
        u = u[deg[u] > 1]
        v = nbr[ptr[u] + rng.integers(0, deg[u])]
        w = nbr[ptr[v] + rng.integers(0, deg[v])]
        k = np.minimum(u, w) * n + np.maximum(u, w)
        pos = np.minimum(np.searchsorted(keys, k), keys.size - 1)
        fresh = (u != w) & (keys[pos] != k)
        ins = np.unique(k[fresh])[:size]
        dele = keys[rng.choice(keys.size, size, replace=False)]
        out.append(([(int(a // n), int(a % n)) for a in ins],
                    [(int(a // n), int(a % n)) for a in dele]))
    return out


def apply_churn(n: int, keys: np.ndarray, batches) -> np.ndarray:
    """The edge set after the batches (deletes before inserts, as
    `update_graph` documents)."""
    keys = keys.copy()
    for ins, dele in batches:
        dk = np.array([min(a, b) * n + max(a, b) for a, b in dele], np.int64)
        keys = keys[~np.isin(keys, dk)]
        ik = np.array([min(a, b) * n + max(a, b) for a, b in ins], np.int64)
        keys = np.union1d(keys, ik)
    return keys


def reference(n: int, keys: np.ndarray, p: np.ndarray) -> np.ndarray:
    """float64 PageRank columns of the undirected edge set `keys`: iterate
    x <- c P x + (1 - c) p with P = A D^-1 (an isolated vertex keeps its
    mass: a self loop) until the L1 step is below REF_TOL. One thread per
    column (scipy's sparse products release the GIL)."""
    import scipy.sparse as sp
    lo, hi = keys // n, keys % n
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    iso = np.flatnonzero(np.bincount(src, minlength=n) == 0)
    src = np.concatenate([src, iso])
    dst = np.concatenate([dst, iso])
    deg = np.bincount(src, minlength=n).astype(np.float64)
    P = sp.csr_matrix((1.0 / deg[src], (dst, src)), shape=(n, n))

    def solve(p_col):
        p_col = p_col / p_col.sum()
        x = p_col
        for _ in range(10_000):
            x_new = C * (P @ x) + (1.0 - C) * p_col
            step = np.abs(x_new - x).sum()
            x = x_new
            if step < REF_TOL:
                break
        return x / x.sum()

    cols = [np.ascontiguousarray(p[:, j]) for j in range(p.shape[1])]
    with ThreadPoolExecutor(max_workers=min(8, len(cols))) as pool:
        return np.stack(list(pool.map(solve, cols)), axis=1)


def seed_matrix(n: int, seed_sets) -> np.ndarray:
    p = np.zeros((n, len(seed_sets)))
    for j, s in enumerate(seed_sets):
        p[list(s), j] = 1.0
    return p


# --------------------------------------------------------------- helpers --

def device_line(jax) -> str:
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind} x{len(jax.devices())}"


def peak_bytes(jax, dev=None):
    stats = (dev or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def make_queries(rng, graph: str, n: int, count: int, qid0: int):
    from repro.serve.pagerank_service import PPRQuery
    return [PPRQuery(qid=qid0 + i, graph=graph,
                     seeds=tuple(int(s) for s in rng.choice(
                         n, int(rng.integers(1, 4)), replace=False)),
                     c=C, tol=TOL, top_k=MAX_TOP_K)
            for i in range(count)]


def compile_serve_solve(jax, svc, name: str):
    """AOT-compile the service's adaptive tick at the widest bucket: the
    compile seconds and the optimized HLO (for the kernel check)."""
    import jax.numpy as jnp
    from repro.serve.pagerank_service import _solve_topk_adaptive
    rg = svc.registry.get(name)
    plan = svc.registry.adaptive_schedule(C, TOL)
    p = jax.ShapeDtypeStruct((rg.n, MAX_BATCH), jnp.float32)
    t0 = time.perf_counter()
    compiled = _solve_topk_adaptive.lower(
        rg.engine, p, plan.c, plan.tol, max_rounds=plan.max_rounds,
        chunk=plan.chunk, k=min(MAX_TOP_K, rg.n)).compile()
    return time.perf_counter() - t0, compiled.as_text(), plan.max_rounds


def drain_timed(svc, queries):
    t0 = time.perf_counter()
    hits = [r for r in (svc.submit(q) for q in queries) if r is not None]
    out = svc.run_until_drained()     # fenced: _harvest blocks on the device
    for r in hits:
        out[r.qid] = r
    return time.perf_counter() - t0, out


def check_answers(fail: Failures, label: str, results, queries, ref,
                  bound: float):
    """Top-k answers against reference columns: each returned score is
    within `bound` of the reference value at its vertex, and the k-th score
    within `bound` of the reference's k-th largest value."""
    worst = 0.0
    ok = True
    for j, q in enumerate(queries):
        r = results.get(q.qid)
        if r is None:
            fail.check(False, f"{label}: query {q.qid} got no answer")
            return
        col = ref[:, j]
        k = len(r.indices)
        top = np.sort(col)[::-1][:k]
        fine = (np.all(np.isfinite(r.scores)) and k == min(q.top_k, col.size)
                and np.unique(r.indices).size == k)
        err = max(float(np.max(np.abs(r.scores - col[r.indices]))),
                  float(np.max(np.abs(r.scores - top))))
        worst = max(worst, err)
        ok = ok and fine and err <= bound
    fail.check(ok, f"{label}: {len(queries)} top-{MAX_TOP_K} answers vs "
                   f"float64 reference, max err {worst:.3e} <= {bound:.3e}")


# ---------------------------------------------------------------- phases --

def serve_phase(jax, fail: Failures, label: str, cfg, graphs: dict,
                plans: dict, refs: dict, rehearse: bool, kernels: bool):
    """One service over `graphs`: queries, updates, repeats, checks."""
    from repro.configs import pagerank_serve
    t0 = time.perf_counter()
    svc = pagerank_serve.make_service(cfg, graphs=graphs)
    print(f"[{label}] registry built in {time.perf_counter() - t0:.1f}s",
          flush=True)
    if rehearse and kernels:
        # the CPU has no Mosaic: run the Pallas kernels in interpret mode
        for name in graphs:
            rg = svc.registry.get(name)
            children, aux = rg.engine.tree_flatten()
            rg.engine = type(rg.engine).tree_unflatten(
                aux[:2] + (True, True), children)
    repeat_hits = 0
    for name, g in graphs.items():
        # no local reference to the engine: an update replaces it, and a
        # stale reference would keep the old tiles on the device
        print(f"[{label}] {name}: engine={svc.registry.get(name).engine.name}"
              f" n={g.n} m={g.m}", flush=True)
        compile_s, hlo, rounds_cap = compile_serve_solve(jax, svc, name)
        n_kernels = hlo.count("tpu_custom_call")
        print(f"[{label}] {name}: serve solve compiled in {compile_s:.1f}s, "
              f"tpu_custom_call x{n_kernels}", flush=True)
        if kernels and not rehearse:
            fail.check(n_kernels > 0, f"{label}/{name}: the fused solve "
                                      "contains tpu_custom_call")
        plan = plans[name]
        t1, out1 = drain_timed(svc, plan["wave1"])
        t2, out2 = drain_timed(svc, plan["wave2"])
        print(f"[{label}] {name}: tick of {len(plan['wave1'])} queries "
              f"first {t1:.3f}s, steady {t2:.3f}s (fenced, host+device, "
              f"{device_line(jax)})", flush=True)
        answered = sum(q.qid in out1 for q in plan["wave1"]) + \
            sum(q.qid in out2 for q in plan["wave2"])
        fail.check(answered == len(plan["wave1"]) + len(plan["wave2"]),
                   f"{label}/{name}: {answered} queries answered")
        epoch0 = svc.registry.get(name).epoch
        for ins, dele in plan["churn"]:
            svc.update_graph(name, insert=ins, delete=dele)
        rg = svc.registry.get(name)
        st = svc.stats
        fail.check(rg.epoch == epoch0 + len(plan["churn"]) and
                   st["incremental_updates"] >= 1,
                   f"{label}/{name}: {len(plan['churn'])} update batches "
                   f"applied (epoch {rg.epoch}, incremental "
                   f"{st['incremental_updates']}, engine {rg.engine.name})")
        hits0 = st["cache_hits"]
        _, rep = drain_timed(svc, plan["repeats"])
        hits = svc.stats["cache_hits"] - hits0
        repeat_hits += hits
        # radius-2 invalidation drops most of a scale-free graph's entries
        # (hub neighbourhoods), so hits are required per service, not per
        # graph
        fail.check(len(rep) == len(plan["repeats"]),
                   f"{label}/{name}: {len(rep)} repeats answered, {hits} "
                   "from the cache after the updates")
        _, fresh = drain_timed(svc, plan["checked"])
        if name not in refs:
            t_ref = time.perf_counter()
            refs[name] = reference(
                g.n, plan["keys_after"],
                seed_matrix(g.n, [q.seeds for q in plan["checked"]]))
            print(f"[{label}] {name}: float64 reference in "
                  f"{time.perf_counter() - t_ref:.1f}s", flush=True)
        check_answers(fail, f"{label}/{name}", fresh, plan["checked"],
                      refs[name], ppr_score_bound(rounds_cap))
    fail.check(repeat_hits > 0, f"{label}: {repeat_hits} repeats served "
                                "from the cache after the updates")
    print(f"[{label}] peak device bytes {peak_bytes(jax)}", flush=True)
    del svc
    gc.collect()


def global_phase(jax, fail: Failures, g):
    from repro.core import make_schedule
    from repro.core.engine import select_engine
    from repro.core.pagerank import cpaa
    eng = select_engine(g, batch=1)
    print(f"[global] chunglu: engine={eng.name} n={g.n} m={g.m}", flush=True)
    sched = make_schedule(C, GLOBAL_TOL)
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = cpaa(eng, c=C, tol=GLOBAL_TOL, schedule=sched)
        jax.block_until_ready(res.pi)
        times.append(time.perf_counter() - t0)
    print(f"[global] cpaa {sched.rounds} rounds: first {times[0]:.3f}s, "
          f"steady {times[1]:.3f}s (fenced, {device_line(jax)})", flush=True)
    pi = np.asarray(res.pi, np.float64)
    t_ref = time.perf_counter()
    ref = reference(g.n, undirected_keys(g), np.ones((g.n, 1)))[:, 0]
    print(f"[global] float64 reference in {time.perf_counter() - t_ref:.1f}s",
          flush=True)
    l1 = float(np.abs(pi - ref).sum())
    bound = global_l1_bound(sched.rounds)
    fail.check(pi.shape == (g.n,) and bool(np.all(np.isfinite(pi)))
               and l1 <= bound,
               f"[global] L1(pi - reference) {l1:.3e} <= {bound:.3e}")
    print(f"[global] peak device bytes {peak_bytes(jax)}", flush=True)


def check_shards(jax, fail: Failures, label: str, eng):
    """Each device holds its own quarter of every partition array."""
    devs = set(jax.devices())
    arrays = [a for a in jax.tree_util.tree_leaves(eng) if a.ndim >= 2]
    ok = bool(arrays)
    for a in arrays:
        shards = a.addressable_shards
        ok = ok and {s.device for s in shards} == devs and \
            all(s.data.size * len(devs) == a.size for s in shards)
    fail.check(ok, f"{label}: {len(arrays)} partition arrays split into one "
                   f"shard per device over {len(devs)} devices")
    for d in sorted(devs, key=lambda d: d.id):
        stats = d.memory_stats()
        used = None if stats is None else stats.get("bytes_in_use")
        print(f"[{label}] device {d.id}: bytes_in_use {used}", flush=True)
        if stats is not None:
            need = sum(s.data.nbytes for a in arrays for s in a.addressable_shards
                       if s.device == d)
            fail.check(used >= need, f"{label}: device {d.id} holds >= its "
                                     f"{need} shard bytes")


def four_chip_phase(jax, fail: Failures, g, cfg, rng, rehearse: bool):
    from repro.configs import pagerank_serve
    from repro.core.engine import ShardedEngine
    n_dev = len(jax.devices())
    if not fail.check(n_dev == 4, f"four devices visible ({n_dev})"):
        return
    queries = make_queries(rng, "mesh", g.n, 64 if not rehearse else 16, 0)
    checked = queries[:8]
    ref = reference(g.n, undirected_keys(g),
                    seed_matrix(g.n, [q.seeds for q in checked]))
    answers = {}
    for mode, grid in (("coo", None), ("sharded-1d", None),
                       ("sharded-2d", (2, 2))):
        label = f"4chip/{mode}"
        svc = pagerank_serve.make_service(
            replace(cfg, engine=mode, mesh_grid=grid), graphs={"mesh": g})
        eng = svc.registry.get("mesh").engine
        print(f"[{label}] engine={eng.name} n={g.n} m={g.m}", flush=True)
        if isinstance(eng, ShardedEngine):
            check_shards(jax, fail, label, eng)
        else:
            placed = {d for a in jax.tree_util.tree_leaves(eng)
                      for d in a.devices()}
            fail.check(len(placed) == 1, f"{label}: arrays on one chip "
                                         f"({sorted(d.id for d in placed)})")
        compile_s, hlo, rounds_cap = compile_serve_solve(jax, svc, "mesh")
        t1, out = drain_timed(svc, queries)
        t2, _ = drain_timed(svc, make_queries(rng, "mesh", g.n,
                                              len(queries), 10_000))
        print(f"[{label}] compile {compile_s:.1f}s; tick of {len(queries)} "
              f"queries first {t1:.3f}s, steady {t2:.3f}s (fenced, "
              f"{device_line(jax)})", flush=True)
        check_answers(fail, label, out, checked, ref,
                      ppr_score_bound(rounds_cap))
        answers[mode] = out
        bound = ppr_score_bound(rounds_cap)
        del svc, eng
        gc.collect()
    worst = max(float(np.max(np.abs(answers[m][q.qid].scores
                                    - answers["coo"][q.qid].scores)))
                for m in ("sharded-1d", "sharded-2d") for q in queries)
    fail.check(worst <= 2 * bound,
               f"4chip: sharded vs one-chip COO top-k scores, max diff "
               f"{worst:.3e} over {len(queries)} queries")


# ------------------------------------------------------------------ main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny graphs on whatever backend JAX has (CPU: "
                         "Pallas interpret mode); never reports ok")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(HERE / "src"))
    import repro
    if not all(Path(d).resolve().is_relative_to(HERE / "src")
               for d in repro.__path__):
        print("chip_smoke: repro was not imported from this checkout",
              file=sys.stderr)
        return 2
    from repro.configs import pagerank_serve
    from repro.graph import generators
    from repro.graph.datasets import chung_lu, scale_dataset
    from repro.launch.compile_cache import enable_compile_cache

    print(f"chip_smoke on {device_line(jax)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    fail = Failures()
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    if args.rehearse:
        mesh = generators.paper_dataset("NACA0015", 0.3)
        chunglu = chung_lu(20_000, avg_deg=16.0, exponent=2.0, seed=SEED)
    else:
        mesh = generators.paper_dataset("NACA0015", 10.0)
        chunglu = scale_dataset("chunglu-1m", cache_dir=HERE / ".dataset-cache")
    print(f"graphs: mesh n={mesh.n} m={mesh.m}; chunglu n={chunglu.n} "
          f"m={chunglu.m} ({time.perf_counter() - t0:.1f}s)", flush=True)
    cfg = replace(pagerank_serve.full_config(), graphs=(), max_batch=MAX_BATCH,
                  max_top_k=MAX_TOP_K, c=C, tol=TOL, adaptive=True)

    phases = []
    if args.four_chips:
        phases.append(("four-chip", lambda: four_chip_phase(
            jax, fail, mesh, cfg, rng, args.rehearse)))
    else:
        n_q = 24 if args.rehearse else 256
        plans = {}
        for name, g in (("mesh", mesh), ("chunglu", chunglu)):
            qs = make_queries(rng, name, g.n, n_q, 0)
            keys = undirected_keys(g)
            churn = churn_batches(g, keys, rng, 2, 2 if args.rehearse else 32)
            plans[name] = dict(
                wave1=qs[:n_q // 2], wave2=qs[n_q // 2:], churn=churn,
                keys_after=apply_churn(g.n, keys, churn),
                repeats=[replace(q, qid=q.qid + 100_000)
                         for q in qs[:max(1, n_q // 10)]],
                checked=make_queries(rng, name, g.n, 8, 200_000))
        refs: dict = {}
        phases += [
            ("serve/auto", lambda: serve_phase(
                jax, fail, "auto", replace(cfg, engine="auto"),
                {"mesh": mesh, "chunglu": chunglu}, plans, refs,
                args.rehearse, kernels=False)),
            ("serve/fused", lambda: serve_phase(
                jax, fail, "fused", replace(cfg, engine="fused"),
                {"mesh": mesh}, plans, refs, args.rehearse, kernels=True)),
            ("global", lambda: global_phase(jax, fail, chunglu)),
        ]
    for name, run in phases:
        try:
            run()
        except Exception:
            traceback.print_exc()
            fail.check(False, f"phase {name} raised")
    print(f"total {time.perf_counter() - t0:.1f}s; peak device bytes "
          f"{peak_bytes(jax)}", flush=True)
    if fail:
        print(f"chip_smoke: {len(fail)} check(s) failed", file=sys.stderr)
        return 1
    if platform != "tpu":
        print("chip_smoke: rehearsal passed; not a chip run, so no result",
              file=sys.stderr)
        return 3
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
