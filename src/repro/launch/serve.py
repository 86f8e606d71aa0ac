"""Serving launcher: batched greedy decoding for any LM --arch, or the
online Personalized-PageRank query service for the pagerank family.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --smoke --requests 8

    PYTHONPATH=src python -m repro.launch.serve --arch pagerank-serve \
        --smoke --requests 64 --updates 2
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs import get


def serve_lm(mod, args):
    from repro.models import transformer as tf
    from repro.serve.engine import Request, ServeEngine

    cfg = mod.smoke_config() if args.smoke else mod.full_config()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, args.max_batch or 4, args.max_len)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(3, 16))).astype(np.int32),
                    max_new_tokens=args.max_new_tokens)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    engine.run_until_drained(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")


def serve_pagerank(mod, args):
    """Mixed query/update workload through the PPR micro-batching service."""
    from repro.serve.pagerank_service import PPRQuery

    from dataclasses import replace
    cfg = mod.serve_config(smoke=args.smoke)
    if args.max_batch:
        cfg = replace(cfg, max_batch=args.max_batch)
    if args.engine:
        cfg = replace(cfg, engine=args.engine)
    if args.weight_dtype:
        cfg = replace(cfg, weight_dtype=None
                      if args.weight_dtype == "float32" else args.weight_dtype)
    if args.mesh_grid:
        r, _, c = args.mesh_grid.partition("x")
        cfg = replace(cfg, mesh_grid=(int(r), int(c)))
    if args.adaptive is not None:
        cfg = replace(cfg, adaptive=args.adaptive)
    if args.adaptive_chunk is not None:
        cfg = replace(cfg, adaptive_chunk=args.adaptive_chunk)
    if args.update_mode:
        cfg = replace(cfg, update_mode=args.update_mode)
    if args.invalidation_radius is not None:
        # negative = blanket flush (the pre-selective behavior)
        cfg = replace(cfg, invalidation_radius=args.invalidation_radius
                      if args.invalidation_radius >= 0 else None)
    if args.scheduler:
        cfg = replace(cfg, scheduler=args.scheduler)
    if args.tenant:
        # --tenant name:priority:deadline_s[:max_depth], repeatable
        rows = []
        for spec in args.tenant:
            parts = spec.split(":")
            if len(parts) not in (3, 4):
                raise SystemExit(f"--tenant {spec!r}: expected "
                                 "name:priority:deadline_s[:max_depth]")
            name, prio, dl = parts[0], int(parts[1]), parts[2]
            depth = int(parts[3]) if len(parts) == 4 else None
            rows.append((name, prio,
                         None if dl in ("inf", "none", "") else float(dl),
                         depth))
        cfg = replace(cfg, tenants=tuple(rows))
    if args.deadline is not None:
        cfg = replace(cfg, default_deadline_s=args.deadline
                      if args.deadline > 0 else None)
    if args.admission_depth is not None:
        cfg = replace(cfg, admission_depth=args.admission_depth
                      if args.admission_depth > 0 else None)
    if args.slack_margin is not None:
        cfg = replace(cfg, slack_margin_s=args.slack_margin)
    if args.async_dispatch is not None:
        cfg = replace(cfg, async_dispatch=args.async_dispatch)
    svc = mod.make_service(cfg)
    names = svc.registry.names()
    engines = {name: svc.registry.get(name).engine.name for name in names}
    print(f"warm graphs + engines: {engines}")
    rng = np.random.default_rng(0)

    queries = []
    for i in range(args.requests):
        name = names[int(rng.integers(0, len(names)))]
        n = svc.registry.get(name).host.n
        seeds = tuple(int(s) for s in
                      rng.choice(n, int(rng.integers(1, 4)), replace=False))
        queries.append(PPRQuery(qid=i, graph=name, seeds=seeds, c=cfg.c,
                                tol=cfg.tol, top_k=min(8, cfg.max_top_k)))
    # ~10% repeats exercise the cache
    repeats = [PPRQuery(qid=args.requests + j, graph=q.graph, seeds=q.seeds,
                        c=q.c, tol=q.tol, top_k=q.top_k)
               for j, q in enumerate(queries[:max(1, args.requests // 10)])]

    from repro.obs import MetricsServer, render_summary, validate_snapshot
    from repro.obs.trace import profiled

    server = None
    if args.metrics_port is not None:
        server = MetricsServer(svc.metrics.registry, port=args.metrics_port,
                               convergence=svc.metrics.convergence,
                               tracer=svc.metrics.tracer).start()
        print(f"metrics: http://127.0.0.1:{server.port}/metrics "
              f"(and /metrics.json)")

    t0 = time.perf_counter()
    results = {}
    with profiled(args.profile_dir):
        for q in queries:
            svc.submit(q)
        results.update(svc.run_until_drained())  # warm cache before the churn
        for u in range(args.updates):
            name = names[u % len(names)]
            # rg.n, not rg.host.n: the vertex count is fixed at registration
            # and reading .host after an in-place patch would force the lazy
            # host Graph to materialize per batch
            n = svc.registry.get(name).n
            edge = (int(rng.integers(0, n // 2)),
                    int(rng.integers(n // 2, n)))
            svc.update_graph(name, insert=[edge])
        for q in repeats:
            svc.submit(q)
        results.update(svc.run_until_drained())
    dt = time.perf_counter() - t0

    # one snapshot feeds every output: the CLI summary below, the JSON
    # dump, and whatever the /metrics endpoint serves while we slept
    mode = "adaptive" if svc.adaptive else "fixed"
    snap = svc.metrics.snapshot(meta={
        "elapsed_s": dt, "arch": args.arch, "mode": mode,
        "scheduler": svc.policy, "async_dispatch": svc.async_dispatch,
        "update_mode": svc.registry.update_mode, "engines": engines,
        "backend": jax.default_backend(),
        "served": len(results),
    })
    if args.metrics_json:
        from repro.obs.export import write_snapshot
        write_snapshot(args.metrics_json, svc.metrics.registry,
                       convergence=svc.metrics.convergence,
                       tracer=svc.metrics.tracer, meta=snap["meta"])
        errs = validate_snapshot(snap)
        if errs:
            raise SystemExit("metrics snapshot failed validation:\n  "
                             + "\n  ".join(errs))
        print(f"metrics snapshot -> {args.metrics_json}")
    print(render_summary(snap))
    if server is not None:
        server.stop()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="LM: engine slots (default 4); pagerank: micro-batch "
                         "width override (default from config)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--updates", type=int, default=0,
                    help="edge-update batches interleaved (pagerank only)")
    ap.add_argument("--engine", default=None,
                    choices=["auto", "coo", "hub-tail", "ell",
                             "block_ell", "fused", "sharded-1d",
                             "sharded-2d"],
                    help="pagerank solve-engine override (default from "
                         "config)")
    ap.add_argument("--weight-dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="packed edge-weight storage dtype (bfloat16 halves "
                         "the weight arrays; accumulation stays f32; "
                         "pagerank only; default from config)")
    ap.add_argument("--mesh-grid", default=None, metavar="RxC",
                    help="sharded-2d grid override, e.g. 2x4 (pagerank only; "
                         "run under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N to simulate a mesh on CPU)")
    ap.add_argument("--adaptive", dest="adaptive", action="store_true",
                    default=None,
                    help="residual-controlled ticks: stop each micro-batch "
                         "solve at tol instead of the a-priori round bound "
                         "(pagerank only; default from config)")
    ap.add_argument("--fixed-rounds", dest="adaptive", action="store_false",
                    help="always run the a-priori round count per tick")
    ap.add_argument("--adaptive-chunk", type=int, default=None,
                    help="rounds between residual checks in adaptive mode "
                         "(default: sized from (c, tol))")
    ap.add_argument("--update-mode", default=None,
                    choices=["incremental", "rebuild"],
                    help="edge-update path: patch the device arrays in "
                         "place (incremental) or rebuild per batch "
                         "(pagerank only; default from config)")
    ap.add_argument("--invalidation-radius", type=int, default=None,
                    help="drop only cached results seeded within this many "
                         "hops of an update's touched vertices and retain "
                         "the rest; negative = blanket flush (pagerank "
                         "only; default from config)")
    ap.add_argument("--scheduler", default=None,
                    choices=["fifo", "deadline"],
                    help="query scheduling policy: arrival-order (fifo) or "
                         "per-tenant EDF with deadline-aware batch closing "
                         "(pagerank only; default from config; see "
                         "docs/scheduling.md)")
    ap.add_argument("--tenant", action="append", default=None,
                    metavar="NAME:PRIO:DL[:DEPTH]",
                    help="declare a tenant class, repeatable: name, "
                         "priority (higher dispatches first at equal "
                         "deadline), default latency budget in seconds "
                         "(inf = no SLO), optional admission depth "
                         "(pagerank only)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="default latency budget in seconds for queries "
                         "whose tenant declares none (<= 0 = unbounded; "
                         "pagerank only)")
    ap.add_argument("--admission-depth", type=int, default=None,
                    help="per-tenant queued-query bound; a full queue "
                         "rejects instead of growing (<= 0 = unbounded; "
                         "pagerank only)")
    ap.add_argument("--slack-margin", type=float, default=None,
                    help="deadline safety margin in seconds: release a "
                         "batch once slack falls to this (pagerank only)")
    ap.add_argument("--async-dispatch", dest="async_dispatch",
                    action="store_true", default=None,
                    help="overlap host batching for tick k+1 with the "
                         "device solve of tick k (pagerank only)")
    ap.add_argument("--sync-dispatch", dest="async_dispatch",
                    action="store_false",
                    help="dispatch and fence each batch in its own tick")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus text at /metrics and the JSON "
                         "snapshot at /metrics.json on this port while the "
                         "workload runs (0 = ephemeral; pagerank only)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the final metrics snapshot (metrics + "
                         "convergence telemetry + recent traces) as JSON "
                         "(pagerank only)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="wrap the workload in jax.profiler.trace writing "
                         "to DIR for TensorBoard/Perfetto deep dives "
                         "(pagerank only)")
    args = ap.parse_args(argv)

    mod = get(args.arch)
    if hasattr(mod, "serve_config"):   # the online PPR query service
        serve_pagerank(mod, args)
    elif getattr(mod, "FAMILY", None) == "lm":
        serve_lm(mod, args)
    else:
        raise SystemExit(
            f"--arch {args.arch} (family {getattr(mod, 'FAMILY', '?')}) is "
            f"not servable; use an LM arch or pagerank-serve")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
