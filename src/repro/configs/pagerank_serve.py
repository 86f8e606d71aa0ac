"""pagerank-serve — the online PPR query service as a registered config.

Unlike the dry-run cells (cpaa-pagerank), this config describes a *serving*
deployment: which graphs are warm in the registry, the (c, tol) operating
point, the micro-batcher width, and the cache budget. launch/serve.py,
examples/serve_pagerank.py and benchmarks/serve_pagerank_bench.py all build
their service through `make_service` so the wiring lives in one place.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax.numpy as jnp

from repro.graph import generators

NAME = "pagerank-serve"
FAMILY = "pagerank"


@dataclass(frozen=True)
class PPRServeConfig:
    # (registry name, generators.PAPER_DATASETS key, scale)
    graphs: tuple[tuple[str, str, float], ...]
    c: float = 0.85
    tol: float = 1e-4
    max_batch: int = 32
    cache_capacity: int = 4096
    max_top_k: int = 16
    # solve-engine format: "auto" (device-count + degree-skew + fill-rate
    # heuristic), "tuned" (measured selection via core/autotune: the
    # workload-bucketed tuning store, measure-on-miss), "coo", "hub-tail",
    # "block_ell", "fused", "sharded-1d" or "sharded-2d" — see
    # core/engine.select_engine and docs/performance.md
    engine: str = "auto"
    # tuned mode only: tuning-store path (None = $REPRO_TUNE_CACHE or
    # ~/.cache/repro_pagerank/tuning.json), per-graph measurement budget in
    # seconds, and whether a store miss falls back to the heuristic instead
    # of measuring (require_cached — for latency-critical starts)
    tune_cache: str | None = None
    tune_budget_s: float = 2.0
    tune_require_cached: bool = False
    # packed storage dtype for edge weights / inv_deg ("bfloat16" halves
    # them; accumulation stays f32). None = solve dtype. Parity bound:
    # L1 <= ~1e-3 on normalized PageRank (the one 1/deg rounding).
    weight_dtype: str | None = None
    # host->device transfer chunk (edges) at registration; None = one shot
    ingest_chunk_edges: int | None = None
    # sharded-engine mesh shape: (R, C) grid for sharded-2d (None = most-
    # square factorization of the device count) and the partition padding
    # lane (vertex chunks are padded to multiples of devices * lane)
    mesh_grid: tuple[int, int] | None = None
    partition_lane: int = 128
    # residual-controlled ticks: exit each micro-batch solve as soon as the
    # measured L1 residual reaches tol instead of always paying the a-priori
    # Formula 8 round count (which stays the hard cap); adaptive_chunk
    # overrides the residual-check period (None = default_chunk(c, tol))
    adaptive: bool = True
    adaptive_chunk: int | None = None
    # edge-update path: "incremental" patches the padded device arrays in
    # place (falling back to a full rebuild when a batch overflows the edge
    # bucket), "rebuild" always takes the full path — see docs/serving.md
    update_mode: str = "incremental"
    # selective cache invalidation: drop only cached results seeded within
    # this many hops of an update's touched vertices, re-stamp the rest to
    # the new epoch (None = blanket flush of the graph's entries)
    invalidation_radius: int | None = 2
    # background re-solve tick: refresh up to this many retained
    # near-boundary entries per idle tick, warm-started from their cached
    # scores via power_refine (0 = off); refresh_rounds power rounds each
    refresh_batch: int = 8
    refresh_rounds: int = 8
    # observability depth (repro.obs): True arms latency/stage histograms,
    # per-query lifecycle traces and convergence telemetry; False keeps
    # only the counters behind the `stats` property. docs/observability.md
    # budgets the detail layer at <5% of us_per_solve.
    metrics_detail: bool = True
    # scheduling tier (docs/scheduling.md): "fifo" is the historical
    # arrival-order policy; "deadline" forms batches per-tenant/per-graph
    # with EDF dispatch and deadline-aware batch closing
    scheduler: str = "fifo"
    # tenant classes as (name, priority, deadline_s, max_depth) rows;
    # deadline_s None = no SLO, max_depth None = the admission_depth bound
    tenants: tuple[tuple[str, int, float | None, int | None], ...] = ()
    # latency budget for queries whose tenant declares none (seconds;
    # None = unbounded)
    default_deadline_s: float | None = None
    # admission control: per-tenant queued-query bound (None = unbounded);
    # a full queue rejects with AdmissionRejected instead of growing
    admission_depth: int | None = None
    # deadline safety margin: a batch releases once its slack (budget minus
    # EWMA solve estimate) falls to this many seconds
    slack_margin_s: float = 0.0
    # overlap host batch formation for tick k+1 with the device solve of
    # tick k (jax async dispatch; the fence moves to harvest time)
    async_dispatch: bool = False


def full_config() -> PPRServeConfig:
    """Production-shaped point: two warm graphs, MXU-width micro-batches."""
    return PPRServeConfig(
        graphs=(("naca", "NACA0015", 1.0), ("kmer", "kmer-V2", 1.0)),
        max_batch=128, cache_capacity=65536, max_top_k=32)


def smoke_config() -> PPRServeConfig:
    return PPRServeConfig(graphs=(("mesh", "NACA0015", 0.12),),
                          max_batch=8, cache_capacity=256, max_top_k=8)


def serve_config(smoke: bool = False) -> PPRServeConfig:
    return smoke_config() if smoke else full_config()


def make_service(cfg: PPRServeConfig, graphs=None):
    """Registry with every configured graph warm + the service over it.

    graphs: optional prebuilt {registry name: Graph} registered INSTEAD of
    generating `cfg.graphs` (a caller serving one large generated graph
    through several services builds it once).
    """
    import math
    from repro.serve.graph_registry import GraphRegistry
    from repro.serve.pagerank_service import PageRankService, ServeMetrics
    from repro.serve.scheduler import TenantSpec
    reg = GraphRegistry(engine=cfg.engine, batch_hint=cfg.max_batch,
                        grid=cfg.mesh_grid,
                        partition_lane=cfg.partition_lane,
                        update_mode=cfg.update_mode,
                        weight_dtype=None if cfg.weight_dtype is None
                        else jnp.dtype(cfg.weight_dtype),
                        ingest_chunk_edges=cfg.ingest_chunk_edges,
                        tune_cache=cfg.tune_cache,
                        tune_budget_s=cfg.tune_budget_s,
                        tune_require_cached=cfg.tune_require_cached)
    if graphs is None:
        graphs = {name: generators.paper_dataset(dataset, scale)
                  for name, dataset, scale in cfg.graphs}
    for name, g in graphs.items():
        reg.register(name, g)
    tenants = [TenantSpec(name=n, priority=p,
                          deadline_s=math.inf if d is None else float(d),
                          max_depth=md)
               for n, p, d, md in cfg.tenants]
    svc = PageRankService(reg, max_batch=cfg.max_batch,
                          cache_capacity=cfg.cache_capacity,
                          max_top_k=cfg.max_top_k,
                          adaptive=cfg.adaptive,
                          adaptive_chunk=cfg.adaptive_chunk,
                          invalidation_radius=cfg.invalidation_radius,
                          refresh_batch=cfg.refresh_batch,
                          refresh_rounds=cfg.refresh_rounds,
                          metrics=ServeMetrics(detail=cfg.metrics_detail),
                          scheduler=cfg.scheduler, tenants=tenants,
                          default_deadline_s=cfg.default_deadline_s,
                          admission_depth=cfg.admission_depth,
                          slack_margin_s=cfg.slack_margin_s,
                          async_dispatch=cfg.async_dispatch)
    reg.schedule(cfg.c, cfg.tol)  # precompute the coefficient vector
    return svc


def cells():
    return []  # online serving workload; not a dry-run (arch x shape) cell


def build(shape: str, multi_pod: bool):
    raise NotImplementedError(
        "pagerank-serve has no dry-run cells; use launch/serve.py")


def smoke_run(seed: int = 0):
    """CPU: tiny mixed query/update workload; service vs dense oracle."""
    from repro.core.pagerank import true_pagerank_dense
    cfg = smoke_config()
    svc = make_service(cfg)
    name = cfg.graphs[0][0]
    g = svc.registry.get(name).host
    rng = np.random.default_rng(seed)
    from repro.serve.pagerank_service import PPRQuery
    seeds = [tuple(int(s) for s in rng.choice(g.n, 2, replace=False))
             for _ in range(5)]
    for i, s in enumerate(seeds):
        svc.submit(PPRQuery(qid=i, graph=name, seeds=s, c=cfg.c, tol=cfg.tol,
                            top_k=4))
    results = svc.run_until_drained()
    # oracle check on query 0
    p = np.zeros(g.n)
    p[list(seeds[0])] = 1.0 / len(seeds[0])
    oracle = true_pagerank_dense(g, cfg.c, p=p)
    top = results[0].indices
    err = np.max(np.abs(results[0].scores - oracle[top]))
    # a repeat hits the cache; an update bumps the epoch
    hit = svc.submit(PPRQuery(qid=99, graph=name, seeds=seeds[0], c=cfg.c,
                              tol=cfg.tol, top_k=4))
    epoch = svc.update_graph(name, insert=[(0, g.n - 1)])
    return {"max_abs_err": jnp.float32(err),
            "cache_hit": jnp.float32(hit is not None and hit.cached),
            "epoch": jnp.float32(epoch),
            "solves": jnp.float32(svc.stats["solves"]),
            # update-path telemetry: in-place patches taken and cache
            # entries that survived the update via selective invalidation
            "updates_incremental": jnp.float32(
                svc.stats["incremental_updates"]),
            "cache_retained": jnp.float32(svc.stats["cache_retained"]),
            # adaptive telemetry: rounds the residual control actually ran
            # vs the a-priori Formula 8 budget across all ticks
            "rounds_used": jnp.float32(svc.stats["rounds_used"]),
            "rounds_bound": jnp.float32(svc.stats["rounds_bound"]),
            "loss": jnp.float32(0.0)}
