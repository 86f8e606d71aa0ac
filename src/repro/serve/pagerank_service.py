"""Online Personalized-PageRank query service with continuous micro-batching.

The offline CPAA solver is throughput-shaped: its three-term recurrence over
a personalization matrix [n, B] is one SpMM per round, which is exactly what
feeds the MXU. This service turns that into an online engine, mirroring the
slot-based LM `ServeEngine` (continuous batching, fixed shapes, one jitted
core per tick):

  * queries (graph name, seed set, c, tol, top_k — plus a tenant class and
    an optional latency budget) pass ADMISSION CONTROL and land in the
    service's scheduler (`serve/scheduler.py`): the historical FIFO policy,
    or per-tenant/per-graph priority queues with deadline-aware batch
    formation (`scheduler="deadline"`) — a batch closes when the oldest
    query's remaining budget minus the EWMA solve-time estimate says it
    must leave, not when the bucket fills. A full queue rejects with a
    counted reason instead of growing without bound;
  * every `tick()` packs one released compatible group — same graph and
    same (c, tol) operating point — into an [n, B] personalization matrix
    and drains it through ONE jitted `cpaa_fixed` call on the graph's
    cached solve engine (COO segment-sum, hub/tail split, or block-ELL
    Pallas SpMM, picked by the registry per epoch — never rebuilt on the
    tick path): B queries cost one batched MXU pass instead of B separate
    solves. Identical in-flight queries collapse to one personalization
    column (each still answered and counted individually);
  * with `adaptive=True` the tick solves through the residual-controlled
    `cpaa_adaptive_fixed` instead: per-query columns that converge stop
    feeding the SpMM, and the tick exits as soon as the measured L1
    residual of every live column reaches tol — never past the a-priori
    Formula 8 round bound, which stays the hard cap;
  * with `async_dispatch=True` the service exploits JAX's asynchronous
    dispatch: a dispatched batch is NOT fenced in its own tick — the next
    tick's host-side work (group selection, twin dedup, building the next
    [n, B] matrix) runs while the device still solves the previous batch,
    and the fence (`block_until_ready`) lands only when that previous
    batch is harvested. Host batching for tick k+1 overlaps the device
    solve of tick k;
  * batch widths are padded up to power-of-two buckets so XLA compiles a
    handful of shapes once and every later tick reuses them;
  * results come back as ranked top-k vertex lists (lax.top_k on device),
    not full [n] vectors — the service answer is "which vertices", and k
    values instead of n keeps the device->host copy O(k * B);
  * an LRU cache keyed by (graph, epoch, seeds, c, tol) serves repeats
    without touching the solver; an EFFECTIVE edge-update batch bumps the
    graph epoch and invalidates — blanket by default, or selectively
    (`invalidation_radius`): only entries seeded within a hop radius of the
    delta's touched vertices are dropped, the rest re-stamped to the new
    epoch, and near-boundary survivors can be refreshed in the background
    (`refresh_tick`) through a warm-started power_refine pass. The refresh
    tick is strictly BACKGROUND work: it yields (defers, counted) whenever
    foreground queries are queued or in flight. A no-op update batch
    (duplicate insert, absent delete) changes nothing and flushes nothing.
    Staleness stays structural, not timed.

Observability (`repro.obs`, see docs/observability.md): every counter the
old flat `stats` dict held is now a labeled metric in a `ServeMetrics`
bundle — the `stats` property derives the same dict from metric totals, so
existing readers keep working. Each query is counted at DISPOSITION time,
exactly once, as one of cache_hit | solved | dropped (the invariant
`queries == cache_hits + solved + dropped` is structural; REJECTED queries
are refused before acceptance and counted separately under
`serve_admission_total`). With `ServeMetrics(detail=True)` (the default)
the service additionally records log-bucketed latency histograms, per-query
lifecycle traces (submit -> queue -> batch_form -> solve_dispatch ->
solve_device -> materialize, the device span fenced via
`jax.block_until_ready` so host dispatch and device execution never alias),
and per-tick convergence telemetry (rounds_used vs the Formula 8 bound,
residual-at-exit, converged column fractions). `detail=False` keeps only
the counters.

Architecture map: docs/architecture.md. Scheduler semantics and tuning:
docs/scheduling.md.
"""
from __future__ import annotations

import math
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.pagerank import cpaa_adaptive_fixed, cpaa_fixed, power_refine
from repro.obs import (ConvergenceLog, MetricsRegistry, NULL_REGISTRY,
                       TickTelemetry, Tracer, UpdateTelemetry)
from repro.obs import export as obs_export
from repro.obs.trace import stage as obs_stage
from repro.serve.graph_registry import GraphRegistry
from repro.serve.result_cache import ResultCache
from repro.serve.scheduler import (AdmissionRejected, DeadlineScheduler,
                                   FifoScheduler, QueueEntry,
                                   SolveTimeEstimator, TenantSpec)

__all__ = ["PPRQuery", "PPRResult", "PageRankService", "ServeMetrics"]


@dataclass(frozen=True)
class PPRQuery:
    """One personalized-PageRank request: restart mass uniform over `seeds`.

    Args:
        qid: caller-chosen id; results are keyed by it.
        graph: registry name of the graph to query.
        seeds: restart vertices (unit mass split uniformly across them).
        c: damping factor of the solve's operating point.
        tol: L1 tolerance of the operating point.
        top_k: how many ranked vertices to return (<= service max_top_k).
        tenant: SLO class label; resolves priority, default deadline and
            the admission bound through the service's `TenantSpec` table.
        deadline_s: per-query latency budget in seconds, overriding the
            tenant default (None = use the tenant's). Only the deadline
            scheduler acts on it; FIFO carries it for metrics only.

    Invariant: seeds are canonicalized (deduped + sorted) at CONSTRUCTION,
    so the cache key and the personalization column the solver builds
    always agree — a query arriving with repeated seeds is the same query
    as its deduped twin, not a different distribution that could alias a
    cached result. `tenant`/`deadline_s` are scheduling attributes and are
    deliberately NOT part of the cache key: the answer depends only on
    (graph, epoch, seeds, c, tol).
    """

    qid: int
    graph: str
    seeds: tuple[int, ...]
    c: float = 0.85
    tol: float = 1e-4
    top_k: int = 8
    tenant: str = "default"
    deadline_s: float | None = None

    def __post_init__(self):
        object.__setattr__(
            self, "seeds", tuple(sorted({int(s) for s in self.seeds})))

    def key(self, epoch: int) -> tuple:
        """Cache key of this query at `epoch`.

        Returns: (graph, epoch, seeds, c, tol) — scheduling attributes
        excluded by design (see class invariant).
        """
        return (self.graph, epoch, self.seeds, float(self.c), float(self.tol))


@dataclass
class PPRResult:
    """Ranked answer to one `PPRQuery`.

    Invariant: `indices`/`scores` are parallel arrays of length `top_k`,
    sorted by descending score; `epoch` is the graph epoch the result is
    valid AT (for retained cache entries that can exceed the epoch it was
    computed at — see docs/serving.md).
    """

    qid: int
    graph: str
    epoch: int
    indices: np.ndarray      # [top_k] int32, ranked by descending score
    scores: np.ndarray       # [top_k] float32, normalized PPR mass
    cached: bool = False
    batch_size: int = 0      # distinct columns in the solve that produced this


class ServeMetrics:
    """The service's observability bundle: metric families + tracer +
    convergence log, all hanging off one `MetricsRegistry`.

    Args:
        registry: `MetricsRegistry` to register families on (None = new).
        detail: True (default) arms the full layer — latency/stage
            histograms, per-query traces, convergence series. False keeps
            only the counters (the histograms come from a disabled registry
            and the tracer hands out null traces), which is the metrics-off
            operating point the <5% overhead budget in docs/observability.md
            is measured against.
        trace_keep: bounded ring size of retained query traces.
        history: bounded length of the convergence time series.

    Invariant: the counter layer is always live — disposition accounting
    (`queries == cache_hits + solved + dropped`) holds at either detail
    level.
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 detail: bool = True, trace_keep: int = 256,
                 history: int = 1024):
        self.registry = MetricsRegistry() if registry is None else registry
        self.detail = detail
        self.tracer = Tracer(enabled=detail, keep=trace_keep)
        self.convergence = ConvergenceLog(keep=history)
        r = self.registry
        hr = r if detail else NULL_REGISTRY   # detail gates the histograms
        self.queries = r.counter(
            "serve_queries_total", "queries accepted by submit()", ("graph",))
        self.served = r.counter(
            "serve_served_total",
            "queries answered, by disposition (cache_hit | solved | dropped)",
            ("graph", "disposition"))
        self.admission = r.counter(
            "serve_admission_total",
            "admission decisions (accept | reject) with machine-readable "
            "reason", ("graph", "tenant", "decision", "reason"))
        self.solves = r.counter(
            "serve_solves_total", "batched device solves",
            ("graph", "engine", "bucket", "mode"))
        self.ticks = r.counter("serve_ticks_total", "micro-batch ticks")
        self.held = r.counter(
            "serve_hold_total",
            "ticks the deadline scheduler held batch formation, betting on "
            "more arrivals")
        self.overlap = r.counter(
            "serve_overlap_dispatch_total",
            "async-dispatch ticks whose host batch formation overlapped an "
            "in-flight device solve")
        self.deadline_miss = r.counter(
            "serve_deadline_miss_total",
            "queries answered after their latency budget expired",
            ("graph", "tenant"))
        self.padded = r.counter(
            "serve_padded_columns_total",
            "pad columns solved (bucket width minus live columns)")
        self.h2d_bytes = r.counter(
            "serve_h2d_bytes_total",
            "bytes of the host arrays ticks hand to the device (the [n, "
            "bucket] float32 personalization)")
        self.updates = r.counter(
            "serve_updates_total", "edge-update batches by effective path",
            ("graph", "kind"))
        self.refreshes = r.counter(
            "serve_refreshes_total", "background warm-start cache refreshes",
            ("graph",))
        self.engine_swaps = r.counter(
            "serve_engine_swaps_total",
            "graph rebuilds that changed the engine class (each resets the "
            "solve-time estimator for the graph)", ("graph",))
        self.refresh_deferred = r.counter(
            "serve_refresh_deferred_total",
            "refresh_tick calls that yielded to pending foreground queries")
        self.cache_dropped = r.counter(
            "serve_cache_dropped_total",
            "cache entries invalidated by graph updates", ("graph",))
        self.cache_retained = r.counter(
            "serve_cache_retained_total",
            "cache entries re-stamped across graph updates", ("graph",))
        self.rounds_used = r.counter(
            "serve_rounds_used_total", "solver rounds actually run",
            ("graph", "mode"))
        self.rounds_bound = r.counter(
            "serve_rounds_bound_total",
            "Formula 8 a-priori round bound accumulated over ticks",
            ("graph", "mode"))
        self.queue_depth = r.gauge(
            "serve_queue_depth", "queries waiting for a tick")
        self.tenant_depth = r.gauge(
            "serve_tenant_depth", "queries queued per tenant class",
            ("tenant",))
        self.solve_ewma = r.gauge(
            "serve_solve_ewma_seconds",
            "EWMA expected batch solve time per (graph, bucket) — the "
            "deadline math's solve-estimate term", ("graph", "bucket"))
        self.latency = hr.histogram(
            "serve_query_latency_seconds", "submit-to-answer e2e latency",
            ("graph", "disposition"))
        self.stage = hr.histogram(
            "serve_stage_seconds",
            "per-tick stage durations (queue is per-query)", ("stage",))
        self.slack = hr.histogram(
            "serve_slack_seconds",
            "dispatch-time slack: latency budget minus expected solve time "
            "(<= 0 lands in the zero bucket)", ("graph",))
        self.solve_seconds = hr.histogram(
            "serve_solve_seconds",
            "dispatch-to-ready batch solve duration (feeds the EWMA "
            "estimator)", ("graph", "bucket"))
        self.refresh_seconds = hr.histogram(
            "serve_refresh_seconds", "per-entry background refresh duration",
            ("graph",))

    def _label_total(self, fam, pos: int, value: str) -> float:
        return sum(inst.value for values, inst in fam.children()
                   if values[pos] == value)

    def disposition_total(self, disposition: str) -> float:
        """Total queries answered under one disposition label."""
        return self._label_total(self.served, 1, disposition)

    def update_kind_total(self, kind: str) -> float:
        """Total edge-update batches of one effective kind."""
        return self._label_total(self.updates, 1, kind)

    def admission_total(self, decision: str) -> float:
        """Total admission decisions of one kind (accept | reject)."""
        return self._label_total(self.admission, 2, decision)

    def snapshot(self, meta: dict | None = None) -> dict:
        """JSON-ready snapshot of metrics + convergence + recent traces."""
        return obs_export.snapshot(self.registry,
                                   convergence=self.convergence,
                                   tracer=self.tracer, meta=meta)


@partial(jax.jit, static_argnames=("rounds", "k"))
def _solve_topk(engine, coeffs: jax.Array, p: jax.Array, rounds: int, k: int):
    """One micro-batch: [n, B] personalization -> ([B, k] ids, [B, k] mass).
    `engine` is the registry's per-(graph, epoch) solve engine; it owns any
    vertex reordering internally, so top-k ids are original vertex ids."""
    pi, _ = cpaa_fixed(engine, coeffs, p, rounds=rounds)
    with jax.named_scope("topk"):
        scores, idx = jax.lax.top_k(pi.T, k)
    return idx.astype(jnp.int32), scores


@partial(jax.jit, static_argnames=("rounds", "k"))
def _refine_topk(engine, x0: jax.Array, p: jax.Array, c, rounds: int, k: int):
    """Warm-started single-column refresh: a few `power_refine` rounds from
    a cached score vector, then re-ranked top-k. The background re-solve
    tick runs retained-but-near-boundary cache entries through this instead
    of a cold CPAA solve (the Chebyshev series cannot be resumed; the power
    recurrence contracts from any warm start)."""
    pi = power_refine(engine, x0, p, c, rounds)
    with jax.named_scope("topk"):
        scores, idx = jax.lax.top_k(pi, k)
    return idx.astype(jnp.int32), scores


@partial(jax.jit, static_argnames=("max_rounds", "chunk", "k"))
def _solve_topk_adaptive(engine, p: jax.Array, c, tol, max_rounds: int,
                         chunk: int, k: int):
    """Adaptive micro-batch: like _solve_topk, but the round count is
    residual-controlled per column — converged query columns stop feeding
    the SpMM, and the tick ends as soon as every live column reaches tol
    (never past the a-priori `max_rounds` cap). Besides the ranked top-k it
    returns the solver telemetry the convergence log records: rounds
    actually run (scalar max over columns), per-column rounds-to-converge,
    and the per-column residual at exit."""
    pi, rounds_used, col_rounds, resid = cpaa_adaptive_fixed(
        engine, p, c, tol, max_rounds=max_rounds, chunk=chunk)
    with jax.named_scope("topk"):
        scores, idx = jax.lax.top_k(pi.T, k)
    return idx.astype(jnp.int32), scores, rounds_used, col_rounds, resid


@dataclass
class _InFlight:
    """One dispatched-but-not-yet-fenced batch solve.

    The device may still be executing it; `idx`/`scores` (and the adaptive
    telemetry) are unfenced jax arrays until `_harvest` blocks on them.
    Everything else is the host-side context needed to materialize results
    after the fence: which queries ride which column, the epoch the solve
    is valid at, and the dispatch timestamps the solve-time EWMA feeds on.
    """

    graph: str
    epoch: int
    rg: object
    live: list                  # [QueueEntry] riding this solve
    cols: dict                  # cache key -> column index
    col_of: list                # per live entry: its column index
    n_reps: int                 # distinct columns (pre-padding)
    b_pad: int
    k: int
    mode: str                   # "adaptive" | "fixed"
    rounds_bound: int
    tol: float
    c: float
    idx: object                 # [B, k] device array (unfenced)
    scores: object              # [B, k] device array (unfenced)
    used: object = None         # adaptive: scalar rounds device array
    resid: object = None        # adaptive: per-column residual device array
    t_dispatch0: float = 0.0    # when the host started dispatching


class PageRankService:
    """Admission control + scheduler + micro-batcher + result cache over a
    `GraphRegistry`.

    Args:
        registry: the `GraphRegistry` owning warm graphs and engines.
        max_batch: widest micro-batch (queries per solve).
        cache_capacity: LRU result-cache entries (0 disables caching).
        max_top_k: largest `top_k` a query may request; cached values hold
            this many entries.
        adaptive: True solves every tick through the residual-controlled
            core — rounds per tick drop to what the measured residual
            demands (never above the a-priori bound).
        adaptive_chunk: residual-check period override (None =
            default_chunk(c, tol) per operating point).
        invalidation_radius: None = an edge update flushes every cached
            result for the graph (blanket, the conservative default). An
            int switches to SELECTIVE invalidation: only entries whose
            seed set lies within that many hops of the update's touched
            vertices are dropped; the rest are re-stamped under the new
            epoch and stay servable (undirected PageRank is
            degree-dominated, so a localized delta perturbs scores locally
            — see docs/serving.md).
        refresh_batch: > 0 arms the background re-solve tick: retained
            entries seeded within `refresh_margin` hops OUTSIDE the drop
            radius are queued, and each `refresh_tick()` warm-starts up to
            this many of them from their cached scores.
        refresh_rounds: floor on power_refine rounds per refresh.
        refresh_margin: width (hops) of the near-boundary refresh ring.
        metrics: `ServeMetrics` bundle (None = a fresh detailed one).
        scheduler: "fifo" (historical policy, the default), "deadline"
            (per-tenant/per-graph EDF queues with deadline-aware batch
            closing), or a ready scheduler instance.
        tenants: iterable/mapping of `TenantSpec`s the deadline scheduler
            resolves query tenants against; unknown tenants get a default
            spec built from `default_deadline_s`/`admission_depth`.
        default_deadline_s: latency budget for queries with no deadline of
            their own whose tenant declares none (None = no deadline).
        admission_depth: per-tenant queued-query bound (FIFO: global
            bound). None = unbounded; a full queue raises
            `AdmissionRejected` (counted, never silent).
        slack_margin_s: deadline safety margin — a batch is released once
            its slack falls to this.
        async_dispatch: True overlaps host batch formation for tick k+1
            with the device solve of tick k (JAX async dispatch; the fence
            moves to harvest time). False (default) keeps the historical
            dispatch-then-fence tick.
        clock: monotonic time source (seconds); injectable for tests.

    Invariant: every ACCEPTED query is answered under exactly one
    disposition (cache_hit | solved | dropped); rejected queries are never
    accepted, so `queries == cache_hits + solved + dropped` is structural
    at any quiescent point (pending/in-flight queries are the difference
    in between).
    """

    def __init__(self, registry: GraphRegistry, max_batch: int = 32,
                 cache_capacity: int = 4096, max_top_k: int = 16,
                 adaptive: bool = False, adaptive_chunk: int | None = None,
                 invalidation_radius: int | None = None,
                 refresh_batch: int = 0, refresh_rounds: int = 8,
                 refresh_margin: int = 1,
                 metrics: ServeMetrics | None = None,
                 scheduler: str | object = "fifo",
                 tenants=None,
                 default_deadline_s: float | None = None,
                 admission_depth: int | None = None,
                 slack_margin_s: float = 0.0,
                 async_dispatch: bool = False,
                 clock=None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.registry = registry
        self.max_batch = max_batch
        self.max_top_k = max_top_k
        self.adaptive = adaptive
        self.adaptive_chunk = adaptive_chunk
        self.invalidation_radius = invalidation_radius
        # refresh_batch > 0 arms the background re-solve tick: retained
        # entries seeded within refresh_margin hops OUTSIDE the drop radius
        # (the near-boundary ring, where the perturbation is largest among
        # the survivors) are queued, and each refresh_tick() warm-starts up
        # to refresh_batch of them from their cached scores through a short
        # power_refine pass (refresh_rounds rounds).
        self.refresh_batch = refresh_batch
        self.refresh_rounds = refresh_rounds
        self.refresh_margin = refresh_margin
        # bounded: an update-only stream (bulk backfill, no query drains)
        # must not grow the queue without limit — when full, the OLDEST
        # keys drop first, which is also the superseded-soonest end
        self._refresh: deque[tuple] = deque(maxlen=4096)
        self.cache = ResultCache(cache_capacity)
        self._clock = clock if clock is not None else time.perf_counter
        self._results: dict[int, PPRResult] = {}
        # power-of-two batch buckets: bounded set of compiled shapes
        self._buckets = []
        b = 1
        while b < max_batch:
            self._buckets.append(b)
            b *= 2
        self._buckets.append(max_batch)
        self.default_deadline_s = default_deadline_s
        self.async_dispatch = async_dispatch
        self._inflight: deque[_InFlight] = deque()
        # tenant table: specs the scheduler and deadline resolution share
        if tenants is None:
            tenants = {}
        elif not isinstance(tenants, dict):
            tenants = {t.name: t for t in tenants}
        self.tenants: dict[str, TenantSpec] = dict(tenants)
        self._default_spec = TenantSpec(
            deadline_s=math.inf if default_deadline_s is None
            else float(default_deadline_s),
            max_depth=admission_depth)
        self.estimator = SolveTimeEstimator()
        if isinstance(scheduler, str):
            if scheduler == "fifo":
                self.scheduler = FifoScheduler(max_batch,
                                               max_depth=admission_depth)
            elif scheduler == "deadline":
                self.scheduler = DeadlineScheduler(
                    max_batch, self.estimator, tenants=self.tenants,
                    default_spec=self._default_spec,
                    max_depth=admission_depth,
                    slack_margin_s=slack_margin_s, bucket=self._bucket)
            else:
                raise ValueError(f"scheduler {scheduler!r} not in "
                                 "('fifo', 'deadline')")
        else:
            self.scheduler = scheduler
        self.policy = getattr(self.scheduler, "name",
                              type(self.scheduler).__name__)
        self.metrics = ServeMetrics() if metrics is None else metrics
        # the registry shares the service's metric registry (build/update/
        # BFS timings, per-graph gauges land next to the serve metrics)
        registry.bind_metrics(self.metrics.registry)
        self._submitted = 0     # total accepted queries (qid autogeneration)
        self._tick_no = 0

    @property
    def stats(self) -> dict:
        """Back-compat counter dict, derived from the metric families.

        Returns: the same keys and meanings as the historical flat dict,
        plus the scheduler-tier counters (`rejected_queries`,
        `deadline_misses`, `held_ticks`, `refresh_deferred`). Point-in-time
        reads of live metric totals — see docs/observability.md for the
        underlying families.
        """
        m = self.metrics
        return {
            "queries": int(m.queries.total()),
            "cache_hits": int(m.disposition_total("cache_hit")),
            "solves": int(m.solves.total()),
            "solved_queries": int(m.disposition_total("solved")),
            "dropped_queries": int(m.disposition_total("dropped")),
            "rejected_queries": int(m.admission_total("reject")),
            "deadline_misses": int(m.deadline_miss.total()),
            "ticks": int(m.ticks.total()),
            "held_ticks": int(m.held.total()),
            "padded_columns": int(m.padded.total()),
            "updates": int(m.updates.total()),
            "rounds_used": int(m.rounds_used.total()),
            "rounds_bound": int(m.rounds_bound.total()),
            "noop_updates": int(m.update_kind_total("noop")),
            "incremental_updates": int(m.update_kind_total("incremental")),
            "cache_dropped": int(m.cache_dropped.total()),
            "cache_retained": int(m.cache_retained.total()),
            "refreshes": int(m.refreshes.total()),
            "refresh_deferred": int(m.refresh_deferred.total()),
        }

    # ---- submission -------------------------------------------------------
    def _tenant_spec(self, tenant: str) -> TenantSpec:
        """Resolve a query's tenant label to its spec (default spec for
        unknown tenants — permissive by design; admission bounds still
        apply through the default)."""
        return self.tenants.get(tenant, self._default_spec)

    def _deadline_budget(self, q: PPRQuery, spec: TenantSpec) -> float:
        """Latency budget resolution: the query's own `deadline_s`, else
        the tenant's, else the service default, else unbounded."""
        if q.deadline_s is not None:
            return float(q.deadline_s)
        if spec.deadline_s != math.inf:
            return float(spec.deadline_s)
        if self.default_deadline_s is not None:
            return float(self.default_deadline_s)
        return math.inf

    def submit(self, q: PPRQuery) -> PPRResult | None:
        """Validate, admit and enqueue a query.

        Args:
            q: the query; its tenant resolves priority/deadline/admission.

        Returns: the `PPRResult` immediately on a cache hit, else None
        (the query is queued; collect it from `tick()` /
        `run_until_drained()` by qid).

        Raises:
            ValueError: empty seeds, out-of-range seed, or top_k over the
                service bound.
            KeyError: unknown graph name.
            AdmissionRejected: the tenant's queue is at its admission
                bound — the query was never accepted (counted under
                `serve_admission_total{decision="reject"}`, not in
                `serve_queries_total`).

        Invariant: acceptance is atomic — a query is counted in `queries`
        iff it was cache-answered or enqueued.
        """
        if not q.seeds:
            raise ValueError("query needs at least one seed vertex")
        rg = self.registry.get(q.graph)
        if min(q.seeds) < 0 or max(q.seeds) >= rg.n:
            raise ValueError(f"seed out of range [0, {rg.n})")
        if q.top_k > self.max_top_k:
            raise ValueError(f"top_k {q.top_k} exceeds service max_top_k "
                             f"{self.max_top_k}")
        m = self.metrics
        t0 = self._clock()
        hit = self.cache.lookup(q.key(rg.epoch))
        if hit is not None:
            # disposition decided here: served from cache, counted once
            m.queries.labels(graph=q.graph).inc()
            self._submitted += 1
            self.cache.count_hit()
            res = self._materialize(q, rg.epoch, *hit, cached=True)
            self._results[q.qid] = res
            m.served.labels(graph=q.graph, disposition="cache_hit").inc()
            m.latency.labels(graph=q.graph, disposition="cache_hit").observe(
                self._clock() - t0)
            tr = m.tracer.start("query", qid=q.qid, graph=q.graph)
            tr.mark("submit")
            tr.begin("cache_hit")
            tr.end("cache_hit")
            m.tracer.finish(tr)
            return res
        # miss is NOT counted yet: this query's disposition (solved at a
        # later tick, twin-filled cache hit, or dropped) is still open
        spec = self._tenant_spec(q.tenant)
        entry = QueueEntry(q=q, t0=t0, tr=None,
                           deadline=t0 + self._deadline_budget(q, spec),
                           tenant=q.tenant, priority=spec.priority)
        try:
            self.scheduler.admit(entry, now=t0)
        except AdmissionRejected as e:
            m.admission.labels(graph=q.graph, tenant=q.tenant,
                               decision="reject", reason=e.reason).inc()
            raise
        m.queries.labels(graph=q.graph).inc()
        m.admission.labels(graph=q.graph, tenant=q.tenant,
                           decision="accept", reason="ok").inc()
        self._submitted += 1
        tr = m.tracer.start("query", qid=q.qid, graph=q.graph)
        tr.mark("submit")
        tr.begin("queue")
        entry.tr = tr
        m.queue_depth.set(self.scheduler.depth())
        m.tenant_depth.labels(tenant=q.tenant).set(
            self.scheduler.depth_for(q.tenant))
        return None

    def submit_many(self, queries) -> list[PPRResult]:
        """Submit a sequence of queries.

        Returns: the results answered synchronously (cache hits), in
        submission order; queued queries arrive via the drain loop.

        Raises: whatever `submit` raises, on the first failing query.
        """
        return [r for r in (self.submit(q) for q in queries) if r is not None]

    # ---- graph updates ----------------------------------------------------
    def update_graph(self, name: str, insert=(), delete=()) -> int:
        """Apply an edge-update batch.

        Args:
            name: registry graph name.
            insert: iterable of (u, v) undirected edges to add.
            delete: iterable of (u, v) undirected edges to remove.

        Returns: the (possibly unchanged) graph epoch after the batch.

        Raises:
            KeyError: unknown graph.
            ValueError: endpoint out of range or a self loop.

        A batch whose effective delta is empty is a true no-op: no epoch
        bump, every cached entry survives (still counted in `updates`).
        Otherwise the epoch bumps and the cache is invalidated — blanket
        (every entry for the graph) when `invalidation_radius` is None,
        selectively when it is set: entries seeded within the radius of the
        delta's touched vertices are dropped, the rest re-stamped under the
        new epoch, and (with the re-solve tick armed) retained entries in
        the near-boundary ring are queued for a warm-started refresh.

        Invariant: any in-flight async batch is harvested FIRST, so every
        result is materialized under the epoch it was solved at.
        """
        self._flush_inflight()
        m = self.metrics
        t0 = self._clock()
        prev_engine = type(self.registry.get(name).engine)
        rg = self.registry.apply_updates(name, insert=insert, delete=delete)
        if type(rg.engine) is not prev_engine:
            # A rebuild picked a different engine: the old EWMAs time a
            # layout that no longer runs, so deadline math restarts cold
            # rather than from stale history.
            self.estimator.reset(graph=name)
            m.engine_swaps.labels(graph=name).inc()
        delta = rg.last_delta
        edges_changed = (len(delta.inserted) + len(delta.deleted)
                         if delta is not None else 0)
        if delta is not None and delta.is_noop:
            m.updates.labels(graph=name, kind="noop").inc()
            m.convergence.record_update(UpdateTelemetry(
                graph=name, kind="noop", edges_changed=0, cache_dropped=0,
                cache_retained=self.cache.count_for(name),
                duration_s=self._clock() - t0))
            return rg.epoch
        kind = "incremental" if rg.last_update_incremental else "rebuild"
        m.updates.labels(graph=name, kind=kind).inc()
        dropped = retained = 0
        if self.invalidation_radius is None or delta is None:
            dropped = self.cache.invalidate_graph(name)
            m.cache_dropped.labels(graph=name).inc(dropped)
        elif self.cache.count_for(name) > 0:
            # one BFS yields both rings: the drop mask and (when the
            # re-solve tick is armed) the refresh ring refresh_margin hops
            # further out
            extra = self.refresh_margin if self.refresh_batch > 0 else 0
            masks = self.registry.hop_neighborhood(
                name, delta.touched, self.invalidation_radius, extra=extra)
            near, ring = masks if extra else (masks, None)

            def drop(key):
                return any(near[s] for s in key[2])

            dropped, retained_keys = self.cache.invalidate_selective(
                name, rg.epoch, drop)
            retained = len(retained_keys)
            m.cache_dropped.labels(graph=name).inc(dropped)
            m.cache_retained.labels(graph=name).inc(retained)
            if ring is not None:
                for key in retained_keys:
                    if any(ring[s] for s in key[2]):
                        self._refresh.append(key)
        m.convergence.record_update(UpdateTelemetry(
            graph=name, kind=kind, edges_changed=edges_changed,
            cache_dropped=dropped, cache_retained=retained,
            duration_s=self._clock() - t0))
        return rg.epoch

    # ---- the background re-solve tick -------------------------------------
    def _refresh_round_count(self, coverage_gap: float, c: float,
                             tol: float) -> int:
        """Rounds so the refreshed entry is within tol of the TRUE new-graph
        PPR. The cache holds only top-k scores, so the warm start carries a
        truncation error of `coverage_gap` (the mass outside the top k) —
        which on spread-out graphs dwarfs the edge-delta perturbation. The
        power recurrence contracts L1 error by c per round from any start,
        so c^rounds * coverage_gap <= tol picks the count that burns the
        truncation off; refresh_rounds is the floor, and the result is
        rounded up to a power of two so jit compiles a bounded shape set.
        (With a well-covered top-k this stays short; with a poor one it
        approaches a plain power solve, which is the honest price of
        correctness — never re-cache a WORSE entry than the one retained.)
        """
        rounds = self.refresh_rounds
        if coverage_gap > tol:
            rounds = max(rounds, int(np.ceil(np.log(tol / coverage_gap)
                                             / np.log(c))))
        return 1 << max(rounds - 1, 0).bit_length()

    def refresh_tick(self, max_entries: int | None = None) -> int:
        """Refresh queued near-boundary cache entries — BACKGROUND work.

        Args:
            max_entries: refresh budget for this call (default
                `refresh_batch`).

        Returns: the number of entries refreshed (0 when the tick yielded).

        Refreshes up to the budget through a warm-started `power_refine`
        pass on the current engine, re-ranking and re-caching in place.
        Entries whose epoch was superseded by a later update, or that were
        evicted meanwhile, are skipped. `run_until_drained` calls this
        after the queue empties when `refresh_batch > 0`; callers can also
        invoke it directly as an idle tick.

        Invariant (foreground yield): if any foreground query is queued or
        in flight, the tick defers — returns 0 immediately, counted under
        `serve_refresh_deferred_total` — and the queued refresh keys stay
        put for the next idle tick. Background refresh work never competes
        with a pending query for the device.
        """
        m = self.metrics
        if self.scheduler.depth() or self._inflight:
            if self._refresh:
                m.refresh_deferred.inc()
            return 0      # yield: foreground queries own the device
        budget = self.refresh_batch if max_entries is None else max_entries
        done = 0
        t_all = self._clock()
        while self._refresh and done < budget:
            key = self._refresh.popleft()
            graph, epoch, seeds, c, tol = key
            rg = self.registry.get(graph)
            if epoch != rg.epoch:
                continue      # a later update superseded this refresh
            hit = self.cache.lookup(key)
            if hit is None:
                continue      # evicted before we got to it
            t0 = self._clock()
            idx, scores = hit
            n = rg.n
            k = min(self.max_top_k, n)
            # warm start: cached top-k mass in place, the unseen remainder
            # spread uniformly (power_refine normalizes)
            gap = max(0.0, 1.0 - float(scores.sum()))
            x0 = np.full(n, gap / n, np.float32)
            x0[idx] += scores
            p = np.zeros(n, np.float32)
            p[list(seeds)] = 1.0
            new_idx, new_scores = _refine_topk(
                rg.engine, jnp.asarray(x0), jnp.asarray(p), c,
                rounds=self._refresh_round_count(gap, c, tol), k=k)
            self.cache.put(key, (np.asarray(new_idx), np.asarray(new_scores)))
            m.refreshes.labels(graph=graph).inc()
            m.refresh_seconds.labels(graph=graph).observe(
                self._clock() - t0)
            done += 1
        if done:
            m.convergence.record_update(UpdateTelemetry(
                graph=graph, kind="refresh", edges_changed=0,
                cache_dropped=0, cache_retained=done,
                duration_s=self._clock() - t_all))
        return done

    # ---- the micro-batcher ------------------------------------------------
    def _bucket(self, b: int) -> int:
        """Smallest compiled batch bucket holding `b` columns."""
        for cap in self._buckets:
            if b <= cap:
                return cap
        return self.max_batch

    def tick(self, now: float | None = None, force: bool = False
             ) -> list[PPRResult]:
        """Run one scheduling step: possibly dispatch one micro-batch,
        possibly harvest a previously dispatched one.

        Args:
            now: scheduler time (default: the service clock) — injectable
                so open-loop drivers and tests control deadline math.
            force: release the most urgent group even if the deadline
                scheduler would hold it for more arrivals (drain mode).

        Returns: the results completed THIS call — twin cache hits
        resolved at batch formation, plus every query of the batch fenced
        this tick (in sync mode, the batch just dispatched; in async mode,
        the PREVIOUS batch — its device solve overlapped this tick's host
        work). May be empty: nothing pending, or the scheduler held.

        Invariant: with `async_dispatch` at most one batch is in flight;
        a tick that dispatches batch k+1 fences batch k before returning.
        """
        m = self.metrics
        now = self._clock() if now is None else now
        out: list[PPRResult] = []
        rec = None
        if self.scheduler.depth():
            with self._stage("schedule", "schedule"):
                group = self.scheduler.next_group(now, force=force)
                if group is None:
                    m.held.inc()
                else:
                    m.ticks.inc()
                    self._tick_no += 1
                    m.queue_depth.set(self.scheduler.depth())
                    rg = self.registry.get(group[0].q.graph)
                    live = self._twin_hits(group, rg.epoch, out)
                    if live and self._inflight:
                        # the device is still solving the previous batch
                        # while this batch forms: the overlap the async
                        # tier buys
                        m.overlap.inc()
            if group is not None and live:
                rec = self._form_and_dispatch(live, rg)
        if rec is not None:
            if self.async_dispatch:
                self._inflight.append(rec)
                if len(self._inflight) > 1:
                    out.extend(self._harvest(self._inflight.popleft()))
            else:
                out.extend(self._harvest(rec))
        elif self._inflight:
            # nothing dispatched this tick: fence the oldest in-flight
            # batch so drains make progress
            out.extend(self._harvest(self._inflight.popleft()))
        for r in out:
            self._results[r.qid] = r
        return out

    def _stage(self, span: str, label: str):
        """Time one tick stage: the profiler span `serve.<span>` and the
        `serve_stage_seconds{stage=label}` histogram, on the service
        clock."""
        return obs_stage(f"serve.{span}", self.metrics.stage.labels(
            stage=label), self._clock)

    def _twin_hits(self, group: list[QueueEntry], epoch: int,
                   out: list[PPRResult]) -> list[QueueEntry]:
        """Answer from cache the queries of a released group whose twin
        filled the cache since submission (appended to `out`); returns the
        rest, which need a solve."""
        m = self.metrics
        # a twin query may have populated the cache since submission — that
        # is this query's disposition: a cache hit, counted here and only
        # here (its submit counted nothing)
        live: list[QueueEntry] = []
        for e in group:
            hit = self.cache.lookup(e.q.key(epoch))
            if hit is not None:
                self.cache.count_hit()
                m.served.labels(graph=e.q.graph,
                                disposition="cache_hit").inc()
                done = self._clock()
                e.tr.end("queue")
                m.latency.labels(graph=e.q.graph,
                                 disposition="cache_hit").observe(done - e.t0)
                m.tracer.finish(e.tr)
                out.append(self._materialize(e.q, epoch, *hit, cached=True))
            else:
                live.append(e)
        return live

    def _form_and_dispatch(self, live: list[QueueEntry], rg) -> _InFlight:
        """Batch formation + device dispatch for the queries of a released
        group that need a solve (`live`, all on graph entry `rg`).

        Returns the in-flight record of the dispatched solve. It is
        UNFENCED: the caller decides when to `_harvest` it (that is the
        async overlap point).
        """
        m = self.metrics
        graph = live[0].q.graph
        epoch = rg.epoch

        # ---- batch formation: identical in-flight queries share a column
        with self._stage("batch_form", "batch_form") as st:
            for e in live:
                queued = e.tr.end("queue")
                m.stage.labels(stage="queue").observe(
                    queued if queued else st.start - e.t0)
                e.tr.begin("batch_form")
            cols: dict[tuple, int] = {}     # cache key -> column index
            col_of: list[int] = []          # per live query
            reps: list[PPRQuery] = []       # representative query per column
            for e in live:
                key = e.q.key(epoch)
                j = cols.get(key)
                if j is None:
                    j = len(reps)
                    cols[key] = j
                    reps.append(e.q)
                col_of.append(j)

            sched, coeffs = self.registry.schedule(live[0].q.c,
                                                   live[0].q.tol)
            n = rg.n
            b_pad = self._bucket(len(reps))
            m.padded.inc(b_pad - len(reps))
            p = np.zeros((n, b_pad), np.float32)
            for j, q in enumerate(reps):
                p[np.asarray(q.seeds, np.int64), j] = 1.0  # canonical at birth
            p[:, len(reps):] = 1.0  # pad columns: uniform mass, discarded
            for e in live:
                e.tr.end("batch_form")

        # dispatch-time slack telemetry: how much budget the most urgent
        # rider had left, net of the expected solve (deadline health)
        deadlines = [e.deadline for e in live if e.deadline != math.inf]
        if deadlines:
            est = self.estimator.estimate(graph, b_pad)
            m.slack.labels(graph=graph).observe(
                min(deadlines) - self._clock() - est)

        # ---- dispatch (host): upload, trace/compile + enqueue on the
        # device stream. JAX dispatch is asynchronous — the jitted call
        # returns with unfenced arrays; the device fence is _harvest's job.
        k = min(self.max_top_k, n)
        mode = "adaptive" if self.adaptive else "fixed"
        with self._stage("dispatch", "solve_dispatch") as dispatch:
            for e in live:
                e.tr.begin("solve_dispatch")
            with self._stage("upload", "upload"):
                p_dev = jnp.asarray(p)
            m.h2d_bytes.inc(p.nbytes)
            used = resid = None
            tol_eff, c_eff = float(live[0].q.tol), float(live[0].q.c)
            if self.adaptive:
                plan = self.registry.adaptive_schedule(
                    live[0].q.c, live[0].q.tol, chunk=self.adaptive_chunk)
                idx, scores, used, _, resid = _solve_topk_adaptive(
                    rg.engine, p_dev, plan.c, plan.tol,
                    max_rounds=plan.max_rounds, chunk=plan.chunk, k=k)
                tol_eff, c_eff = plan.tol, plan.c
            else:
                idx, scores = _solve_topk(rg.engine, coeffs, p_dev,
                                          rounds=sched.rounds, k=k)
            for e in live:
                e.tr.end("solve_dispatch")
        return _InFlight(graph=graph, epoch=epoch, rg=rg, live=live,
                         cols=cols, col_of=col_of, n_reps=len(reps),
                         b_pad=b_pad, k=k, mode=mode,
                         rounds_bound=sched.rounds, tol=tol_eff, c=c_eff,
                         idx=idx, scores=scores, used=used, resid=resid,
                         t_dispatch0=dispatch.start)

    def _harvest(self, rec: _InFlight) -> list[PPRResult]:
        """Fence one in-flight batch and materialize its results.

        Blocks on the device (`jax.block_until_ready`), feeds the measured
        dispatch-to-ready duration into the solve-time EWMA, settles each
        rider's disposition/latency/deadline accounting, fills the cache,
        and records the tick's convergence telemetry.

        Returns: one `PPRResult` per live query of the batch.
        """
        m = self.metrics
        graph, epoch = rec.graph, rec.epoch

        # ---- device: the only fence — dispatch was async, so device
        # execution time is exactly what block_until_ready waits out here.
        # JL006's allowlist (repro.analysis LintConfig.blocking_allowed)
        # names this function; a blocking call anywhere else in the serve
        # path is a lint error, not a judgment call.
        with self._stage("fence", "solve_device") as fence:
            for e in rec.live:
                e.tr.begin("solve_device", kind="device")
            jax.block_until_ready(rec.scores)
            for e in rec.live:
                e.tr.end("solve_device")

        # ---- materialize: host copies, cache fills, per-query results
        out: list[PPRResult] = []
        with self._stage("materialize", "materialize"):
            for e in rec.live:
                e.tr.begin("materialize")
            idx = np.asarray(rec.idx)
            scores = np.asarray(rec.scores)
            for key, j in rec.cols.items():
                self.cache.put(key, (idx[j], scores[j]))
            for i, e in enumerate(rec.live):
                # disposition: served by this solve (twins included — each
                # query counts itself, the COLUMNS were deduplicated)
                self.cache.count_miss()
                m.served.labels(graph=e.q.graph, disposition="solved").inc()
                j = rec.col_of[i]
                out.append(self._materialize(e.q, epoch, idx[j], scores[j],
                                             cached=False,
                                             batch_size=rec.n_reps))
                e.tr.end("materialize")
                done = self._clock()
                m.latency.labels(graph=e.q.graph,
                                 disposition="solved").observe(done - e.t0)
                if done > e.deadline:
                    m.deadline_miss.labels(graph=e.q.graph,
                                           tenant=e.tenant).inc()
                m.tracer.finish(e.tr)

        with self._stage("telemetry", "telemetry"):
            # the EWMA the deadline scheduler plans with: dispatch-to-ready,
            # i.e. what a batch riding this (graph, bucket) should expect
            t_solve = fence.end - rec.t_dispatch0
            self.estimator.observe(graph, rec.b_pad, t_solve)
            m.solve_seconds.labels(graph=graph, bucket=rec.b_pad).observe(
                t_solve)
            m.solve_ewma.labels(graph=graph, bucket=rec.b_pad).set(
                self.estimator.estimate(graph, rec.b_pad))

            rounds_used = int(rec.used) if rec.used is not None \
                else rec.rounds_bound
            engine_name = type(rec.rg.engine).__name__
            m.solves.labels(graph=graph, engine=engine_name, bucket=rec.b_pad,
                            mode=rec.mode).inc()
            m.rounds_used.labels(graph=graph, mode=rec.mode).inc(rounds_used)
            m.rounds_bound.labels(graph=graph, mode=rec.mode).inc(
                rec.rounds_bound)

            # ---- convergence telemetry: the paper's bound, checked per tick
            if rec.resid is not None:
                r_live = np.asarray(rec.resid)[:rec.n_reps]
                residual = float(r_live.max()) if r_live.size else 0.0
                converged = float(np.mean(r_live <= rec.tol)) \
                    if r_live.size else 1.0
            else:
                residual = 0.0      # fixed path: no residual is measured
                converged = 1.0     # by construction of the a-priori bound
            m.convergence.record_tick(TickTelemetry(
                tick=self._tick_no, graph=graph, engine=engine_name,
                bucket=rec.b_pad, columns=rec.n_reps,
                rounds_used=rounds_used, rounds_bound=rec.rounds_bound,
                residual=residual, converged_frac=converged, tol=rec.tol,
                c=rec.c))
        return out

    def _flush_inflight(self) -> None:
        """Fence and materialize every in-flight batch (results land in
        the delivery buffer). Called before graph updates so no result is
        materialized under a bumped epoch, and by overrun drains so solved
        work is delivered, not dropped."""
        while self._inflight:
            for r in self._harvest(self._inflight.popleft()):
                self._results[r.qid] = r

    def _materialize(self, q: PPRQuery, epoch: int, idx: np.ndarray,
                     scores: np.ndarray, cached: bool,
                     batch_size: int = 0) -> PPRResult:
        return PPRResult(qid=q.qid, graph=q.graph, epoch=epoch,
                         indices=idx[:q.top_k].copy(),
                         scores=scores[:q.top_k].copy(),
                         cached=cached, batch_size=batch_size)

    # ---- drain loop -------------------------------------------------------
    def pending(self) -> int:
        """Accepted queries not yet answered: queued in the scheduler plus
        riding an unfenced in-flight batch."""
        return self.scheduler.depth() + sum(len(rec.live)
                                            for rec in self._inflight)

    def _drop_pending(self, max_ticks: int) -> None:
        """Overrun policy "drop": discard the undrained queue, counting and
        warning instead of raising. Dropped queries get no result."""
        m = self.metrics
        entries = self.scheduler.drain()
        n_drop = len(entries)
        now = self._clock()
        tenants = set()
        for e in entries:
            m.served.labels(graph=e.q.graph, disposition="dropped").inc()
            m.latency.labels(graph=e.q.graph, disposition="dropped").observe(
                now - e.t0)
            e.tr.end("queue")
            e.tr.mark("dropped")
            m.tracer.finish(e.tr)
            tenants.add(e.tenant)
        m.queue_depth.set(0)
        for t in tenants:
            m.tenant_depth.labels(tenant=t).set(0)
        warnings.warn(
            f"PPR serve loop dropped {n_drop} undrained queries after "
            f"{max_ticks} ticks (see serve_served_total"
            '{disposition="dropped"})', RuntimeWarning, stacklevel=3)

    def run_until_drained(self, max_ticks: int = 10_000,
                          on_overrun: str = "raise") -> dict[int, PPRResult]:
        """Tick until the queue AND the in-flight pipeline are empty.

        Args:
            max_ticks: bound on drain iterations.
            on_overrun: "raise" (default) raises RuntimeError when the
                queue outlives `max_ticks`; "drop" discards the remainder,
                counts each under the `dropped_queries` disposition, and
                warns. In-flight batches are always harvested — solved
                work is delivered, never dropped.

        Returns: the delivery buffer of results completed since the last
        drain — including cache hits resolved at submit() time — cleared
        on return, so a long-running service does not accumulate every
        result it ever produced. Keyed by qid.

        Raises:
            ValueError: unknown `on_overrun` policy.
            RuntimeError: overrun with on_overrun="raise".

        Drain ticks run with `force=True` — no further arrivals can widen
        a batch, so the deadline scheduler's hold heuristic is moot. A
        drain that finishes in exactly `max_ticks` ticks is NOT an
        overrun. When `refresh_batch > 0` the background refresh tick runs
        after the drain (the queue is idle by then — the yield invariant).
        """
        if on_overrun not in ("raise", "drop"):
            raise ValueError(f"on_overrun {on_overrun!r} not in "
                             "('raise', 'drop')")
        ticks = 0
        while self.scheduler.depth() or self._inflight:
            if ticks >= max_ticks:
                if on_overrun == "raise":
                    raise RuntimeError(
                        f"PPR serve loop did not drain: "
                        f"{self.pending()} queries still in flight after "
                        f"{max_ticks} ticks")
                self._flush_inflight()   # solved work is never dropped
                self._drop_pending(max_ticks)
                break
            self.tick(force=True)
            ticks += 1
        if self.refresh_batch > 0:
            self.refresh_tick()   # idle work: near-boundary cache refreshes
        out, self._results = self._results, {}
        return out

    def query(self, graph: str, seeds, c: float = 0.85, tol: float = 1e-4,
              top_k: int = 8, qid: int | None = None) -> PPRResult:
        """Synchronous convenience wrapper: submit one query and drain it.

        Args:
            graph: registry graph name.
            seeds: restart vertices.
            c, tol, top_k: the query's operating point and answer size.
            qid: explicit id (default: a fresh negative id).

        Returns: the ranked `PPRResult` (cached or freshly solved).

        Raises: everything `submit`/`run_until_drained` raise.
        """
        qid = qid if qid is not None else -1 - self._submitted
        res = self.submit(PPRQuery(qid=qid, graph=graph,
                                   seeds=tuple(int(s) for s in seeds),
                                   c=c, tol=tol, top_k=top_k))
        if res is not None:
            self._results.pop(qid, None)  # delivered here, not via drain
            return res
        return self.run_until_drained()[qid]
