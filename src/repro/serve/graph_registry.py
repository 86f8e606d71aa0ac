"""Named, warm graphs for the online PPR query service.

The registry is the serving-side owner of graph state:

  * every graph is registered under a name and kept device-resident
    (`DeviceGraph`) so queries never pay a host->device transfer;
  * each registered graph carries a **solve engine** (`core.engine`), built
    once per (graph, epoch) by `select_engine` and cached on the
    RegisteredGraph — the micro-batcher drains every tick through it with no
    per-tick format rebuilds. Block-ELL engines are built with power-of-two
    slot padding so edge updates rarely change jit shapes; sharded engines
    (multi-device CPAA) build their mesh partition here, so the [n, B] query
    batches drain through a sharded solve per tick;
  * each registered graph carries an **epoch** counter. Edge-update batches
    (insert/delete of undirected edges) rebuild the device graph + engine
    and bump the epoch; result caches key on (name, epoch), so stale
    entries can never be served after an update;
  * `ChebSchedule`s are precomputed per (c, tol) — the coefficient vector
    depends only on the damping factor and tolerance, not on the graph, so
    one schedule warms every graph at that operating point. Schedules also
    come in an **adaptive mode** (`adaptive_schedule`): the same a-priori
    round count, but consumed as a hard CAP by the residual-controlled
    `cpaa_adaptive_fixed`, plus the residual-check chunk size — the
    micro-batcher's per-tick round count then drops to whatever the
    measured residual demands instead of always paying the Formula 8 bound.

Edge updates come in two flavours, selected by `update_mode`:

  * **incremental** (default) — `apply_updates` computes the batch's
    `EdgeDelta` (O(batch log m), no pass over the edge set) and, when the
    changed slots fit the current power-of-two edge bucket, PATCHES the
    device graph in place through the host `EdgeSlots` mirror: only the
    affected slots of the padded src/dst/weight arrays and the touched rows
    of inv_deg are rewritten — no host set-op rebuild, no engine reselect,
    no solver retrace. The engine is kept current via its `refresh(delta)`
    hook (free for COO; block-ELL re-tiles reusing its BFS perm; sharded
    engines repartition on their existing mesh).
  * **rebuild** — every batch takes the historical full path: numpy set ops
    on the canonical keys, `from_undirected_edges`, fresh DeviceGraph +
    `select_engine`. The incremental mode falls back to exactly this when a
    batch overflows the bucket (the bucket then grows).

A batch whose effective delta is EMPTY (duplicate inserts, deletes of
absent edges) is detected before any of that and is a true no-op: no
rebuild, no epoch bump, so downstream result caches keep every entry.
Device edge arrays are padded to power-of-two buckets (zero-weight pad
edges), so updates only retrace the solve when m crosses a bucket
boundary, not on every batch.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.chebyshev import ChebSchedule, default_chunk, make_schedule
from repro.core.engine import CooEngine, EllEngine, select_engine
from repro.graph.ops import (DeviceGraph, EdgeSlots, device_graph,
                             patch_device_graph)
from repro.graph.structure import EdgeDelta, Graph, edge_delta
from repro.obs.metrics import NULL_REGISTRY, MetricsRegistry

__all__ = ["AdaptiveSchedule", "RegisteredGraph", "GraphRegistry",
           "UPDATE_MODES"]

UPDATE_MODES = ("incremental", "rebuild")


@dataclass(frozen=True)
class AdaptiveSchedule:
    """Operating point of one residual-controlled solve.

    max_rounds is the a-priori Formula 8 round count — the adaptive solver
    treats it as a hard cap, so an adaptive tick can never run more rounds
    than a fixed-round tick at the same (c, tol); chunk is the residual-
    check period (see core.chebyshev.default_chunk).
    """

    c: float
    tol: float
    max_rounds: int
    chunk: int


class RegisteredGraph:
    """One serving graph: host copy (for rebuilds), device copy (for solves),
    the solve engine picked for it, and the epoch stamped into every cache
    key. `engine` is refreshed (or rebuilt with `dg`) on every effective
    update, so it is always the (graph, epoch)-current format — ticks reuse
    it as-is. `keys` is the sorted canonical undirected key set and `slots`
    the host mirror of the padded device arrays (None when the registered
    graph broke the symmetrized-edge contract, which forces rebuilds);
    `last_delta` / `last_update_incremental` record what the most recent
    update batch actually did.

    `host` is a LAZY view after an in-place update: the COO serving path
    never reads the host Graph per batch, so the incremental path marks it
    stale and the next reader (an engine refresh that re-tiles, a test
    oracle, the CSR fallback) materializes it from the slot mirror."""

    def __init__(self, name: str, host: Graph, dg: DeviceGraph,
                 engine=None, epoch: int = 0, keys=None, slots=None):
        self.name = name
        self._host = host
        self._host_stale = False
        self.dg = dg
        self.engine = engine
        self.epoch = epoch
        self.keys = keys
        self.slots = slots
        self.last_delta: EdgeDelta | None = None
        self.last_update_incremental = False
        self._csr_cache = None

    @property
    def host(self) -> Graph:
        if self._host_stale:
            self._host = self.slots.to_graph()
            self._host_stale = False
        return self._host

    @host.setter
    def host(self, g: Graph) -> None:
        self._host = g
        self._host_stale = False

    @property
    def n(self) -> int:
        return self._host.n      # the vertex set is fixed at registration


def _undirected_keys(g: Graph) -> np.ndarray:
    """Canonical int64 keys lo * n + hi of the undirected edge set (each
    edge once; self loops — the isolated-vertex patch — excluded)."""
    lo = np.minimum(g.src, g.dst).astype(np.int64)
    hi = np.maximum(g.src, g.dst).astype(np.int64)
    keep = lo < hi
    return np.unique(lo[keep] * g.n + hi[keep])


def _edge_bucket(m: int, minimum: int = 1024) -> int:
    """Smallest power of two >= m (at least `minimum`): the padded device
    edge-array length. <= 2x memory for shape stability across updates."""
    b = minimum
    while b < m:
        b *= 2
    return b


def _edges_to_keys(n: int, edges) -> np.ndarray:
    """[(u, v), ...] -> canonical keys; validates vertex ids."""
    arr = np.asarray(list(edges), np.int64).reshape(-1, 2)
    if arr.size and (arr.min() < 0 or arr.max() >= n):
        raise ValueError(f"edge endpoint out of range [0, {n})")
    if np.any(arr[:, 0] == arr[:, 1]):
        raise ValueError("self loops are not valid undirected edges")
    lo = np.minimum(arr[:, 0], arr[:, 1])
    hi = np.maximum(arr[:, 0], arr[:, 1])
    return np.unique(lo * n + hi)


class _RegistryObs:
    """The registry's instrument bundle. Built against NULL_REGISTRY by
    default (every call a no-op), swapped for live families when a metrics
    registry is bound — the service binds its own at construction so
    build/update/BFS timings land next to the serve metrics."""

    def __init__(self, reg: MetricsRegistry):
        self.build_seconds = reg.histogram(
            "registry_build_seconds",
            "DeviceGraph + engine (re)build duration per (graph, epoch)",
            ("graph",))
        self.update_seconds = reg.histogram(
            "registry_update_seconds",
            "apply_updates duration by effective path", ("graph", "path"))
        self.hop_seconds = reg.histogram(
            "registry_hop_bfs_seconds",
            "hop_neighborhood BFS duration", ("graph",))
        self.epoch = reg.gauge(
            "graph_epoch", "current epoch per registered graph", ("graph",))
        self.edges = reg.gauge(
            "graph_edges", "undirected edge count per registered graph",
            ("graph",))
        self.engine_info = reg.gauge(
            "graph_engine_info",
            "1 for the engine class currently serving the graph",
            ("graph", "engine"))
        self.slot_fill = reg.gauge(
            "graph_engine_slot_fill",
            "live neighbour slots over n * K of the ELL engine's table "
            "(0 once the graph has left the ELL engine)",
            ("graph",))
        self.hub_edge_share = reg.gauge(
            "graph_engine_hub_edge_share",
            "hub edges over all directed edges in the hub/tail engine's "
            "split (0 once the graph has left the hub/tail engine)",
            ("graph",))
        self.panel_fill = reg.gauge(
            "graph_engine_panel_fill",
            "hub edges over the hub/tail engine's panel slots (0 once the "
            "graph has left the hub/tail engine)",
            ("graph",))
        self.tail_slot_fill = reg.gauge(
            "graph_engine_tail_slot_fill",
            "tail edges over the hub/tail engine's tail slots: its degree "
            "slices' table slots and copied self-loop rows (0 once the "
            "graph has left the hub/tail engine)",
            ("graph",))

    def set_graph_gauges(self, rg: "RegisteredGraph") -> None:
        self.epoch.labels(graph=rg.name).set(rg.epoch)
        self.edges.labels(graph=rg.name).set(
            len(rg.keys) if rg.keys is not None else rg.host.m)
        current = type(rg.engine).__name__
        for values, inst in self.engine_info.children():
            if values[0] == rg.name:    # a rebuild may have switched class
                inst.set(0.0)
        self.engine_info.labels(graph=rg.name, engine=current).set(1.0)
        for attr in ("slot_fill", "hub_edge_share", "panel_fill",
                     "tail_slot_fill"):
            gauge, value = getattr(self, attr), getattr(rg.engine, attr, None)
            if value is not None:
                gauge.labels(graph=rg.name).set(value)
            elif any(v[0] == rg.name for v, _ in gauge.children()):
                # a rebuild moved the graph to another engine
                gauge.labels(graph=rg.name).set(0.0)


class GraphRegistry:
    """Name -> RegisteredGraph, plus the shared (c, tol) schedule cache.

    Args:
        dtype: accumulation dtype of device graphs and solves.
        engine: engine selection mode for `select_engine` ("auto" picks
            COO / hub-tail / ELL / block-ELL / sharded per graph shape; any
            other mode forces that engine).
        batch_hint: expected micro-batch width, steering auto selection.
        mesh, grid, partition_lane: sharded-engine placement knobs.
        update_mode: "incremental" (in-place device patch when the batch
            fits the edge bucket) or "rebuild" (always the full path).
        weight_dtype: packed storage dtype for edge weights / inv_deg
            (None = `dtype`); accumulation stays in `dtype`.
        ingest_chunk_edges: host->device transfer chunk at registration
            (None = one shot).

    Invariant: `rg.engine` is always current for (graph, epoch) — every
    effective update refreshes or rebuilds it before the epoch bump
    returns, so the tick path never reselects or retraces formats.
    """

    def __init__(self, dtype=jnp.float32, engine: str = "auto",
                 batch_hint: int | None = None, mesh=None,
                 grid: tuple[int, int] | None = None,
                 partition_lane: int = 128,
                 update_mode: str = "incremental",
                 weight_dtype=None,
                 ingest_chunk_edges: int | None = None):
        if update_mode not in UPDATE_MODES:
            raise ValueError(f"update_mode {update_mode!r} not in "
                             f"{UPDATE_MODES}")
        self.dtype = dtype
        self.engine_mode = engine
        self.batch_hint = batch_hint  # expected micro-batch width (auto mode)
        # sharded-engine knobs: the mesh the solves run on (default: all
        # devices), the (R, C) grid for sharded-2d, and the partition lane
        self.mesh = mesh
        self.grid = grid
        self.partition_lane = partition_lane
        self.update_mode = update_mode
        # packed storage dtype for edge weights / inv_deg on the COO and
        # hub-tail paths (None = dtype); accumulation stays in `dtype`
        self.weight_dtype = None if weight_dtype is None \
            else jnp.dtype(weight_dtype)
        # host->device transfer chunk for register(): bounds the peak extra
        # host allocation at registration of paper-scale graphs (None = one
        # shot; see graph.ops._chunked_device_1d)
        self.ingest_chunk_edges = ingest_chunk_edges
        self._graphs: dict[str, RegisteredGraph] = {}
        self._schedules: dict[tuple[float, float], tuple[ChebSchedule, jax.Array]] = {}
        self._adaptive: dict[tuple[float, float, int | None], AdaptiveSchedule] = {}
        self._obs = _RegistryObs(NULL_REGISTRY)

    def bind_metrics(self, registry: MetricsRegistry) -> None:
        """Point the registry's instrumentation at a live MetricsRegistry
        (idempotent; called by PageRankService with its own). Gauges for
        already-registered graphs are published immediately."""
        self._obs = _RegistryObs(registry)
        for rg in self._graphs.values():
            self._obs.set_graph_gauges(rg)

    def _build(self, g: Graph):
        """(DeviceGraph, engine, EdgeSlots) for one epoch of a graph. The
        COO engine reuses the padded device graph; block-ELL engines pad
        their slot count so the solve keeps stable jit shapes across
        epochs; sharded engines rebuild their mesh partition here — per
        (graph, epoch), never on the tick path. The EdgeSlots host mirror
        is what later updates patch through (None if the graph breaks the
        symmetrized contract — those graphs always rebuild)."""
        try:
            slots = EdgeSlots.from_graph(g, cap=_edge_bucket(g.m))
        except ValueError:
            slots = None
        dg = slots.to_device(self.dtype, weight_dtype=self.weight_dtype,
                             chunk_edges=self.ingest_chunk_edges) \
            if slots is not None else \
            device_graph(g, self.dtype, pad_edges_to=_edge_bucket(g.m),
                         weight_dtype=self.weight_dtype,
                         chunk_edges=self.ingest_chunk_edges)
        eng = select_engine(g, batch=self.batch_hint, mode=self.engine_mode,
                            dg=dg, dtype=self.dtype, stable_shapes=True,
                            mesh=self.mesh, grid=self.grid,
                            lane=self.partition_lane,
                            weight_dtype=self.weight_dtype)
        return dg, eng, slots

    # ---- graphs -----------------------------------------------------------
    def register(self, name: str, g: Graph) -> RegisteredGraph:
        """Register `g` under `name`: build its device graph + engine once
        and keep them warm (epoch 0).

        Returns: the new `RegisteredGraph`.

        Raises:
            ValueError: the name is already registered (re-registration
                would silently orphan cached epochs — update instead).
        """
        if name in self._graphs:
            raise ValueError(f"graph {name!r} already registered")
        t0 = time.perf_counter()
        dg, eng, slots = self._build(g)
        self._obs.build_seconds.labels(graph=name).observe(
            time.perf_counter() - t0)
        rg = RegisteredGraph(name=name, host=g, dg=dg, engine=eng,
                             keys=_undirected_keys(g), slots=slots)
        self._graphs[name] = rg
        self._obs.set_graph_gauges(rg)
        return rg

    def get(self, name: str) -> RegisteredGraph:
        """The registered graph for `name`.

        Raises:
            KeyError: unknown name (the message lists the known ones).
        """
        if name not in self._graphs:
            raise KeyError(f"unknown graph {name!r}; known: {sorted(self._graphs)}")
        return self._graphs[name]

    def names(self) -> list[str]:
        """Sorted names of every registered graph."""
        return sorted(self._graphs)

    # ---- dynamic updates --------------------------------------------------
    def apply_updates(self, name: str, insert=(), delete=()) -> RegisteredGraph:
        """Apply a batch of undirected edge inserts/deletes.

        Duplicate inserts and deletes of absent edges are no-ops; a batch
        whose EFFECTIVE delta is empty changes nothing — no rebuild, no
        epoch bump (so caches keyed on the epoch keep every entry). The
        vertex set is fixed at registration.

        With `update_mode="incremental"` an effective batch that fits the
        current edge bucket is applied as an in-place device patch + engine
        refresh; otherwise (mode "rebuild", bucket overflow, or a graph
        without an EdgeSlots mirror) the full rebuild runs. Either way the
        epoch bumps exactly once per effective batch, and `rg.last_delta`
        reports which edges/vertices moved — the serving layer keys its
        selective cache invalidation off `last_delta.touched`.
        """
        t0 = time.perf_counter()
        rg = self.get(name)
        n = rg.n
        ins = _edges_to_keys(n, insert) if len(insert) else \
            np.empty(0, np.int64)
        dele = _edges_to_keys(n, delete) if len(delete) else \
            np.empty(0, np.int64)
        if rg.keys is None:
            rg.keys = _undirected_keys(rg.host)
        delta = edge_delta(n, rg.keys, ins, dele)
        rg.last_delta = delta
        rg.last_update_incremental = False
        if delta.is_noop:
            self._obs.update_seconds.labels(graph=name, path="noop").observe(
                time.perf_counter() - t0)
            return rg

        patch = None
        if self.update_mode == "incremental" and rg.slots is not None:
            patch = rg.slots.apply_delta(delta)
        if patch is not None:
            patch_device_graph(rg.dg, patch)
            rg._host_stale = True   # materialized on next read, not per batch
            if isinstance(rg.engine, (CooEngine, EllEngine)):
                # COO shares rg.dg, already patched in place; ELL edits its
                # own table from the delta (and hands over to COO on rg.dg
                # when the table outgrows its bar) — neither forces the
                # host Graph materialization
                rg.engine = rg.engine.refresh(None, delta, dg=rg.dg)
            else:
                rg.engine = rg.engine.refresh(rg.host, delta, dg=rg.dg,
                                              stable_shapes=True,
                                              lane=self.partition_lane)
            # the mirror maintains the sorted key set incrementally; alias
            # it (apply_delta replaces, never mutates, its key array)
            rg.keys = rg.slots.ekeys
            rg.last_update_incremental = True
        else:
            # fallback: merge the sorted key set (memcpy-sized delete/insert
            # at searchsorted positions, not set ops over m) and rebuild
            keys = np.delete(rg.keys,
                             np.searchsorted(rg.keys, delta.deleted))
            keys = np.insert(keys, np.searchsorted(keys, delta.inserted),
                             delta.inserted)
            g_new = Graph.from_undirected_edges(n, keys // n, keys % n)
            rg.host = g_new
            t_build = time.perf_counter()
            rg.dg, rg.engine, rg.slots = self._build(g_new)
            self._obs.build_seconds.labels(graph=name).observe(
                time.perf_counter() - t_build)
            rg.keys = keys
        rg.epoch += 1
        rg._csr_cache = None
        path = "incremental" if rg.last_update_incremental else "rebuild"
        self._obs.update_seconds.labels(graph=name, path=path).observe(
            time.perf_counter() - t0)
        self._obs.set_graph_gauges(rg)
        return rg

    def hop_neighborhood(self, name: str, vertices, radius: int,
                         extra: int = 0):
        """Boolean [n] mask of every vertex within `radius` hops of
        `vertices` on the CURRENT host graph (radius 0 = the set itself).
        With `extra > 0`, returns (mask, outer_mask) where outer_mask
        extends the walk `extra` more hops — both rings from ONE BFS, so
        the serving layer's drop radius and refresh ring don't each pay a
        sweep.

        Vectorized BFS over the always-current edge-slot mirror (O(hops *
        cap) boolean work, no per-update CSR re-sort; measured faster than
        a device segment-sum hop — XLA CPU scatter-add is serial over the
        edge list), falling back to a sorted-src host CSR cached per epoch.
        This is the locality primitive behind selective cache invalidation:
        entries seeded inside the mask are the ones a localized edge delta
        can have perturbed beyond tolerance.
        """
        t0 = time.perf_counter()
        rg = self.get(name)
        n = rg.n
        mask = np.zeros(n, bool)
        v = np.asarray(vertices, np.int64)
        total_hops = max(radius, 0) + max(extra, 0)
        if v.size:
            mask[v] = True
        # inner ring snapshot (taken mid-walk; pre-seeded when radius <= 0)
        inner = mask.copy() if extra > 0 and radius <= 0 else None

        def walk_slots():
            nonlocal mask
            src, dst, live = rg.slots.src, rg.slots.dst, rg.slots.live
            for _ in range(total_hops):
                hit = mask[src] & live
                grew = np.zeros(n, bool)
                grew[dst[hit]] = True
                grew &= ~mask
                if not grew.any():
                    return
                mask |= grew
                yield

        def walk_csr():
            if rg._csr_cache is None:
                g = rg.host
                order = np.argsort(g.src, kind="stable")
                counts = np.bincount(g.src, minlength=n).astype(np.int64)
                row_start = np.concatenate([np.zeros(1, np.int64),
                                            np.cumsum(counts)[:-1]])
                rg._csr_cache = (row_start, counts, g.dst[order])
            row_start, counts, dst_sorted = rg._csr_cache
            frontier = v
            for _ in range(total_hops):
                cnt = counts[frontier]
                total = int(cnt.sum())
                if total == 0:
                    return
                # flat gather of every frontier vertex's CSR range
                starts = np.repeat(row_start[frontier], cnt)
                offs = np.arange(total) - np.repeat(
                    np.cumsum(cnt) - cnt, cnt)
                nbrs = dst_sorted[starts + offs]
                new = np.unique(nbrs[~mask[nbrs]])
                if new.size == 0:
                    return
                mask[new] = True
                frontier = new
                yield

        hops_done = 0
        if v.size and total_hops:
            for _ in (walk_slots() if rg.slots is not None else walk_csr()):
                hops_done += 1
                if extra > 0 and hops_done == radius and inner is None:
                    inner = mask.copy()
        self._obs.hop_seconds.labels(graph=name).observe(
            time.perf_counter() - t0)
        if extra <= 0:
            return mask
        return (mask if inner is None else inner), mask

    # ---- schedules --------------------------------------------------------
    def schedule(self, c: float, tol: float) -> tuple[ChebSchedule, jax.Array]:
        """Precomputed (ChebSchedule, device coeff vector) for (c, tol)."""
        key = (float(c), float(tol))
        if key not in self._schedules:
            sched = make_schedule(c, tol)
            self._schedules[key] = (sched, jnp.asarray(sched.coeffs, self.dtype))
        return self._schedules[key]

    def adaptive_schedule(self, c: float, tol: float,
                          chunk: int | None = None) -> AdaptiveSchedule:
        """Adaptive-mode schedule for (c, tol): the a-priori round count as
        the hard cap plus the residual-check chunk (default sized by
        `default_chunk`). Cached like the fixed-round schedules."""
        key = (float(c), float(tol), chunk)
        if key not in self._adaptive:
            sched, _ = self.schedule(c, tol)
            self._adaptive[key] = AdaptiveSchedule(
                c=float(c), tol=float(tol), max_rounds=sched.rounds,
                chunk=default_chunk(float(c), float(tol)) if chunk is None
                else int(chunk))
        return self._adaptive[key]
