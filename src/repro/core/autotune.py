"""Measured engine selection: workload-bucketed autotuner + tuning store.

`select_engine(mode="auto")` picks a format from hand-set constants
(SHARDED_MIN_N, HUB_TAIL_MIN_N, the fill-rate bar) that were tuned once on
CPU. Those bars are deliberately conservative, which makes them wrong in
measurable places — hub/tail already beats COO well below HUB_TAIL_MIN_N on
skewed graphs, and whether block-ELL pays off at a given fill depends on
the backend and batch width. This module replaces the guess with a
measurement, in three pieces:

  * `WorkloadKey` — the bucketing scheme tuning results are keyed by:
    log2 buckets of n and m, a degree-skew band from the same
    `_hub_edge_fraction` probe the heuristic uses, the power-of-two batch
    bucket, and the (backend, device_count) pair. Graphs of the same shape
    class share a key, so one measurement generalizes: a restarted service
    serving a structurally-similar graph skips straight to the stored
    winner.
  * `Autotuner` — on a store miss, short-lists the feasible candidates
    (device count for the sharded engines, int32 range, a memory census
    from the tile-fill probe for block-ELL), builds each with the caller's
    exact build knobs, and times K warm Chebyshev rounds (SpMM +
    `cheb_round`, the solve hot path) with `block_until_ready` fences —
    min-over-reps, compile excluded by a warm-up call. The winner is picked
    by `pick_winner`, whose deterministic tie-break prefers the heuristic's
    choice whenever it measures within `jitter_tol` of the best, so
    mode="tuned" can never lose to mode="auto" by more than measurement
    jitter. XLA's compiled cost analysis (flops / bytes accessed, the
    `launch/dryrun.py` scaffolding) is recorded per candidate where the
    backend exposes it.
  * `TuningStore` — the versioned on-disk JSON the measurements persist in
    (atomic tmp-file + os.replace writes, `$REPRO_TUNE_CACHE` override,
    same pattern as the graph/datasets preprocessed-binary cache). A
    corrupt, truncated or version-mismatched file is treated as empty and
    the tuner falls back to measuring (or, with `require_cached=True`, to
    the heuristic) — never to half-read state. Entries record the backend,
    device count and jax version they were measured under. The store also
    caches the `block_fill_rate` probe per (graph fingerprint, block) so
    auto mode stops re-running the host BFS + tile census for graphs it
    has already probed.

Every decision is counted (`autotune_decisions_total`, by source) so a
warm-store service start can be ASSERTED to perform zero tuning solves.
"""
from __future__ import annotations

import dataclasses
import json
import hashlib
import math
import os
import time
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (ENGINE_MODES, HubTailEngine,
                               _hub_edge_fraction, heuristic_mode,
                               select_engine)
from repro.graph.structure import Graph
from repro.obs.metrics import NULL_REGISTRY, MetricsRegistry

__all__ = [
    "TUNE_FORMAT_VERSION",
    "WorkloadKey",
    "TuneDecision",
    "TuningStore",
    "FillProbeCache",
    "Autotuner",
    "default_tune_path",
    "default_tuner",
    "graph_fingerprint",
    "log2_bucket",
    "pick_winner",
    "process_probe_cache",
]

# Bump to orphan every stored measurement AND fill probe: the loader treats
# any other version as a miss and the next save rewrites the whole file
# (mirror of graph/datasets.CACHE_FORMAT_VERSION). The CI actions/cache key
# (`tuning-v1-...` in .github/workflows/ci.yml) tracks this number.
TUNE_FORMAT_VERSION = 1

# Degree-skew bands for the workload key, over the fraction of directed
# edges whose destination is a hub (deg >= HubTailEngine.DEFAULT_MIN_DEG):
# meshes/grids score ~0.0 (band 0), the chung-lu scale-free operating point
# ~0.65 (band 2), extreme hub graphs band 3. The 0.4 edge coincides with
# HUB_TAIL_MIN_EDGE_FRAC so the heuristic's own decision boundary never
# cuts through the middle of a bucket.
SKEW_BANDS = (0.1, 0.4, 0.7)

# Candidate measurement order AFTER the heuristic's pick (which always goes
# first so an exhausted budget still leaves a valid winner): cheapest build
# first, sharded last (their partition builds dominate on big graphs).
CANDIDATE_ORDER = ("coo", "hub_tail", "fused", "sharded_1d", "sharded_2d")


def log2_bucket(x: int) -> int:
    """The log2 size bucket of a count: bit_length, so [2^k, 2^(k+1)) share
    a bucket. Used for both workload keys and the registry's re-tune check
    (an edge-update stream re-tunes only when m crosses a bucket edge)."""
    return int(x).bit_length()


def default_tune_path() -> Path:
    """$REPRO_TUNE_CACHE, or ~/.cache/repro_pagerank/tuning.json. A value
    without a .json suffix is treated as a directory holding tuning.json."""
    env = os.environ.get("REPRO_TUNE_CACHE")
    base = Path(env) if env else \
        Path.home() / ".cache" / "repro_pagerank" / "tuning.json"
    if base.suffix != ".json":
        base = base / "tuning.json"
    return base


def graph_fingerprint(g: Graph, max_edges: int = 1 << 16) -> str:
    """Content hash of a graph for the fill-probe cache: (n, m) exactly,
    plus the edge arrays (strided down to <= max_edges samples above that —
    a collision then needs identical n, m AND identical sampled edges, and
    the consequence of one is only a suboptimal format pick, never a wrong
    result)."""
    h = hashlib.sha1()
    h.update(np.asarray([g.n, g.m], np.int64).tobytes())
    stride = max(1, int(g.m) // max_edges)
    h.update(np.ascontiguousarray(np.asarray(g.src)[::stride]).tobytes())
    h.update(np.ascontiguousarray(np.asarray(g.dst)[::stride]).tobytes())
    return h.hexdigest()[:16]


class FillProbeCache:
    """In-process cache of `block_fill_rate` results keyed by
    (graph fingerprint, block) — the no-disk probe cache auto mode uses so
    serving epoch bumps stop re-running the host BFS + tile census for
    shapes already probed. `TuningStore` implements the same two-method
    interface backed by its JSON file."""

    def __init__(self):
        self._fills: dict[str, float] = {}

    @staticmethod
    def _key(g: Graph, block: int) -> str:
        return f"{graph_fingerprint(g)}/b{int(block)}"

    def get_fill(self, g: Graph, block: int) -> float | None:
        return self._fills.get(self._key(g, block))

    def put_fill(self, g: Graph, block: int, fill: float) -> None:
        self._fills[self._key(g, block)] = float(fill)


_PROCESS_PROBE_CACHE = FillProbeCache()


def process_probe_cache() -> FillProbeCache:
    """The process-wide in-memory fill-probe cache (auto-mode default)."""
    return _PROCESS_PROBE_CACHE


class TuningStore:
    """Versioned on-disk JSON holding tuning entries + fill probes.

    Load is lazy and non-throwing: a missing file is an empty store, and a
    corrupt/truncated/version-mismatched file is ALSO an empty store with
    `load_error` set — the tuner then measures afresh (or falls back to the
    heuristic under `require_cached`), and the next `put` atomically
    rewrites the whole file at the current version. Writes go through a
    same-directory tmp file + os.replace, so a crash mid-write leaves
    either the old file or the new one, never a half-written store.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        p = default_tune_path() if path is None else Path(path)
        if p.suffix != ".json":
            p = p / "tuning.json"
        self.path = p
        self._data: dict | None = None
        self.load_error: str | None = None

    def _empty(self) -> dict:
        return {"version": TUNE_FORMAT_VERSION, "entries": {},
                "fill_probes": {}}

    def _load(self) -> dict:
        if self._data is not None:
            return self._data
        self.load_error = None
        try:
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict) or \
                    data.get("version") != TUNE_FORMAT_VERSION:
                self.load_error = "version"
                data = self._empty()
            else:
                data.setdefault("entries", {})
                data.setdefault("fill_probes", {})
        except FileNotFoundError:
            data = self._empty()
        except (OSError, json.JSONDecodeError, UnicodeDecodeError,
                ValueError):
            self.load_error = "corrupt"
            data = self._empty()
        self._data = data
        return data

    def _save(self) -> None:
        data = self._load()
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_name(self.path.name + ".tmp")
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    # ---- tuning entries ---------------------------------------------------
    def get(self, key: str) -> dict | None:
        entry = self._load()["entries"].get(key)
        return entry if isinstance(entry, dict) else None

    def put(self, key: str, entry: dict) -> None:
        self._load()["entries"][key] = entry
        self._save()

    def entries(self) -> dict[str, dict]:
        return dict(self._load()["entries"])

    # ---- fill probes (same interface as FillProbeCache) -------------------
    def get_fill(self, g: Graph, block: int) -> float | None:
        v = self._load()["fill_probes"].get(FillProbeCache._key(g, block))
        return float(v) if isinstance(v, (int, float)) else None

    def put_fill(self, g: Graph, block: int, fill: float) -> None:
        self._load()["fill_probes"][FillProbeCache._key(g, block)] = \
            float(fill)
        self._save()


@dataclasses.dataclass(frozen=True)
class WorkloadKey:
    """The shape class a tuning measurement generalizes over (see module
    docstring). `as_str` is the store key; it embeds the format version so
    a semantic change to the bucketing orphans old entries by key, not by
    accident."""

    n_bucket: int
    m_bucket: int
    skew_bucket: int
    batch_bucket: int
    backend: str
    device_count: int

    @classmethod
    def from_graph(cls, g: Graph, batch: int | None = None, *,
                   backend: str | None = None,
                   device_count: int | None = None) -> "WorkloadKey":
        frac = _hub_edge_fraction(g, HubTailEngine.DEFAULT_MIN_DEG)
        b = 1
        target = 1 if batch is None else max(1, int(batch))
        while b < target:
            b *= 2
        return cls(
            n_bucket=log2_bucket(g.n),
            m_bucket=log2_bucket(g.m),
            skew_bucket=sum(frac >= edge for edge in SKEW_BANDS),
            batch_bucket=b.bit_length() - 1,
            backend=jax.default_backend() if backend is None else backend,
            device_count=jax.device_count() if device_count is None
            else int(device_count))

    @property
    def batch(self) -> int:
        """Representative batch width of the bucket (its upper edge)."""
        return 1 << self.batch_bucket

    def as_str(self) -> str:
        return (f"v{TUNE_FORMAT_VERSION}/{self.backend}"
                f"/d{self.device_count}/n{self.n_bucket}/m{self.m_bucket}"
                f"/s{self.skew_bucket}/b{self.batch_bucket}")


@dataclasses.dataclass
class TuneDecision:
    """What the tuner decided and why. `engine` is the already-built winner
    when the decision came from a fresh measurement (the caller reuses it
    instead of rebuilding); None on a store hit or heuristic fallback, in
    which case the caller builds `mode` itself. `us_per_iter` is the
    winner's measured per-round time (None when nothing was measured) —
    the serving layer seeds its solve-time estimator from it."""

    mode: str
    source: str            # store_hit | measured | fallback_heuristic
    key: str
    engine: object | None = None
    us_per_iter: float | None = None
    heuristic: str | None = None


def pick_winner(measured: dict[str, float], heuristic: str,
                jitter_tol: float = 0.10) -> str:
    """Deterministic winner over a {mode: seconds} measurement dict.

    The fastest mode wins, EXCEPT that the heuristic's pick is kept
    whenever it measured within `jitter_tol` of the best — so mode="tuned"
    matches mode="auto" up to measurement jitter by construction, and only
    deviates on a real, beyond-jitter win. Exact ties (and the argmin
    itself) break by CANDIDATE_ORDER position, never dict order, so the
    same measurements always pick the same engine.
    """
    if not measured:
        return heuristic
    order = {m: i for i, m in enumerate(CANDIDATE_ORDER)}
    best = min(measured, key=lambda m: (measured[m], order.get(m, len(order))))
    t_h = measured.get(heuristic)
    if t_h is not None and t_h <= measured[best] * (1.0 + jitter_tol):
        return heuristic
    return best


def _round_once(eng, x, t, acc):
    # one solve-loop round: SpMM + Chebyshev recurrence/accumulation — the
    # exact per-iteration hot path, so fused engines show their cheb_step
    # win and sharded engines pay their real collectives
    y = eng.apply(x)
    return eng.cheb_round(y, t, acc, 0.5)


_ROUND = jax.jit(_round_once)


def _time_round(eng, x, t, acc, reps: int) -> float:
    """Min-over-reps wall time of one warm round, fenced."""
    jax.block_until_ready(_ROUND(eng, x, t, acc))   # compile + warm-up
    best = math.inf
    for _ in range(max(1, int(reps))):
        t0 = time.perf_counter()
        jax.block_until_ready(_ROUND(eng, x, t, acc))
        best = min(best, time.perf_counter() - t0)
    return best


def _describe(e: BaseException, limit: int = 300) -> str:
    """'ExcType: message', clipped — what a store entry records about a
    failure."""
    return f"{type(e).__name__}: {e}"[:limit]


def _cost_summary(eng, x, t, acc) -> dict | None:
    """flops / bytes-accessed of the compiled round where the backend
    exposes cost analysis (the launch/dryrun.py lower+compile scaffolding);
    None where it doesn't — informational, never load-bearing."""
    try:
        cost = _ROUND.lower(eng, x, t, acc).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        out = {k: float(cost[k]) for k in ("flops", "bytes accessed")
               if k in cost}
        return out or None
    except Exception:
        return None


class _TunerObs:
    """Tuner instrument bundle: built against NULL_REGISTRY (no-ops) until
    a live metrics registry is bound — same pattern as _RegistryObs."""

    def __init__(self, reg: MetricsRegistry):
        self.decisions = reg.counter(
            "autotune_decisions_total",
            "engine-selection decisions by source (store_hit | measured | "
            "fallback_heuristic | sticky)", ("graph", "source"))
        self.us_per_iter = reg.gauge(
            "autotune_us_per_iter",
            "measured per-round time of the engine the tuner selected",
            ("graph", "engine"))
        self.measure_seconds = reg.histogram(
            "autotune_measure_seconds",
            "wall time of one full candidate measurement pass", ("graph",))


class Autotuner:
    """Measure-or-remember engine selection (see module docstring).

    Args:
        store: the `TuningStore` to consult/persist (None = the default
            `$REPRO_TUNE_CACHE` path).
        reps: warm timed rounds per candidate (min is taken).
        budget_s: wall-clock cap on one measurement pass — the heuristic's
            pick is always measured first, so exhausting the budget leaves
            a valid (possibly heuristic) winner and records which
            candidates were skipped.
        jitter_tol: tie-break width of `pick_winner`.
        require_cached: never measure — a store miss (including a corrupt
            or missing store file) falls back to the heuristic. The
            zero-tuning operating point for latency-critical starts.
    """

    # feasibility bars for the candidate shortlist: the block-ELL values
    # tensor estimate (4 bytes * m / fill, from the same tile census the
    # heuristic probes) must fit, and engines whose build cost can't pay
    # off on tiny graphs aren't worth timing at all
    MAX_TILE_BYTES = 1 << 30
    MIN_CANDIDATE_N = 1 << 10

    def __init__(self, store: TuningStore | None = None, *, reps: int = 3,
                 budget_s: float = 5.0, jitter_tol: float = 0.10,
                 require_cached: bool = False):
        self.store = TuningStore() if store is None else store
        self.reps = int(reps)
        self.budget_s = float(budget_s)
        self.jitter_tol = float(jitter_tol)
        self.require_cached = bool(require_cached)
        # plain counts mirror of the decision counter metric, for callers
        # (and tests) without a bound metrics registry
        self.decision_counts: dict[str, int] = {}
        self._obs = _TunerObs(NULL_REGISTRY)

    def bind_metrics(self, registry: MetricsRegistry) -> None:
        """Point the tuner's instrumentation at a live MetricsRegistry
        (idempotent; the serving registry forwards its own)."""
        self._obs = _TunerObs(registry)

    def record(self, source: str, graph: str, mode: str,
               us_per_iter: float | None = None) -> None:
        """Count one selection decision (also called by the serving
        registry for its sticky per-epoch reuse of a tuned winner)."""
        self.decision_counts[source] = \
            self.decision_counts.get(source, 0) + 1
        self._obs.decisions.labels(graph=graph, source=source).inc()
        if us_per_iter is not None:
            self._obs.us_per_iter.labels(graph=graph, engine=mode).set(
                us_per_iter)

    def measured_count(self) -> int:
        """Decisions that ran a measurement pass — zero on a warm store."""
        return self.decision_counts.get("measured", 0)

    # ---- the decision -----------------------------------------------------
    def tune(self, g: Graph, batch: int | None = None, *,
             graph_name: str = "graph", dg=None, dtype=jnp.float32,
             block: int = 128, min_fill: float | None = None,
             use_kernel: bool | None = None, interpret: bool | None = None,
             stable_shapes: bool = False, mesh=None,
             grid: tuple[int, int] | None = None, lane: int = 128,
             comm_dtype=None, sharded_min_n: int | None = None,
             weight_dtype=None) -> TuneDecision:
        """Select the engine mode for (g, batch) — store hit, measurement,
        or heuristic fallback. Build knobs mirror `select_engine` and are
        used verbatim for candidate builds, so a freshly measured winner
        (`TuneDecision.engine`) is directly usable by the caller."""
        n_dev = int(mesh.devices.size) if mesh is not None \
            else jax.device_count()
        key = WorkloadKey.from_graph(g, batch=batch, device_count=n_dev)
        ks = key.as_str()
        build_kw = dict(dg=dg, dtype=dtype, block=block, min_fill=min_fill,
                        use_kernel=use_kernel, interpret=interpret,
                        stable_shapes=stable_shapes, mesh=mesh, grid=grid,
                        lane=lane, comm_dtype=comm_dtype,
                        sharded_min_n=sharded_min_n,
                        weight_dtype=weight_dtype)
        heuristic = heuristic_mode(g, batch, block=block, min_fill=min_fill,
                                   mesh=mesh, sharded_min_n=sharded_min_n,
                                   probe_cache=self.store)

        entry = self.store.get(ks)
        if entry is not None and entry.get("engine") in ENGINE_MODES:
            us = entry.get("us_per_iter")
            self.record("store_hit", graph_name, entry["engine"], us)
            return TuneDecision(mode=entry["engine"], source="store_hit",
                                key=ks, us_per_iter=us, heuristic=heuristic)

        if self.require_cached:
            self.record("fallback_heuristic", graph_name, heuristic)
            return TuneDecision(mode=heuristic, source="fallback_heuristic",
                                key=ks, heuristic=heuristic)

        t0 = time.perf_counter()
        try:
            measured, engines, skipped = self._measure_candidates(
                g, key, heuristic, n_dev, build_kw)
        except Exception as e:
            # a failed measurement pass must never take selection down
            # with it: the zero-cost tier is always available — but the
            # failure is reported, never mistaken for a slow candidate
            warnings.warn(f"autotune measurement pass for {graph_name!r} "
                          f"failed ({_describe(e)}); using the heuristic's "
                          f"{heuristic!r}", RuntimeWarning, stacklevel=2)
            self.record("fallback_heuristic", graph_name, heuristic)
            return TuneDecision(mode=heuristic, source="fallback_heuristic",
                                key=ks, heuristic=heuristic)
        self._obs.measure_seconds.labels(graph=graph_name).observe(
            time.perf_counter() - t0)
        if not measured:
            self.record("fallback_heuristic", graph_name, heuristic)
            return TuneDecision(mode=heuristic, source="fallback_heuristic",
                                key=ks, heuristic=heuristic)

        winner = pick_winner(measured, heuristic, self.jitter_tol)
        us = measured[winner] * 1e6
        self.store.put(ks, {
            "engine": winner,
            "us_per_iter": round(us, 2),
            "candidates": {m: round(s * 1e6, 2)
                           for m, s in sorted(measured.items())},
            "heuristic": heuristic,
            "skipped": skipped,
            "reps": self.reps,
            # environment stamp: keyed by (backend, device_count) already,
            # recorded redundantly so a store file is self-describing
            "backend": key.backend,
            "device_count": key.device_count,
            "jax": jax.__version__,
        })
        self.record("measured", graph_name, winner, us)
        return TuneDecision(mode=winner, source="measured", key=ks,
                            engine=engines.get(winner), us_per_iter=us,
                            heuristic=heuristic)

    # ---- candidates -------------------------------------------------------
    def _shortlist(self, g: Graph, key: WorkloadKey, heuristic: str,
                   n_dev: int, block: int) -> list[str]:
        """Feasible candidate modes, heuristic's pick first."""
        from repro.graph.ops import check_int32_range
        cands = ["coo"]
        try:
            check_int32_range(g.n, g.m, what="autotune candidates")
        except ValueError:
            return cands
        big_enough = g.n >= self.MIN_CANDIDATE_N
        if big_enough and \
                _hub_edge_fraction(g, HubTailEngine.DEFAULT_MIN_DEG) > 0.0:
            cands.append("hub_tail")
        if g.n >= 2 * block:
            fill = self.store.get_fill(g, block)
            if fill is None:
                from repro.graph.structure import block_fill_rate
                fill, _ = block_fill_rate(g, block=block)
                self.store.put_fill(g, block, fill)
            # memory census from the tile probe: the [n_rb, S, B, B] values
            # tensor is ~ m * 4 bytes / fill — refuse to even build it when
            # the estimate blows the cap (scattered graphs at scale)
            if fill > 0.0 and 4.0 * g.m / fill <= self.MAX_TILE_BYTES:
                cands.append("fused")
        if n_dev >= 2 and big_enough:
            cands.append("sharded_1d")
            if n_dev >= 4:
                cands.append("sharded_2d")
        ordered = [m for m in CANDIDATE_ORDER
                   if m in cands and m != heuristic]
        return ([heuristic] if heuristic in cands else []) + ordered

    def _measure_candidates(self, g: Graph, key: WorkloadKey, heuristic: str,
                            n_dev: int, build_kw: dict):
        """Build + time each shortlisted candidate within the budget.
        Returns ({mode: seconds}, {mode: engine}, {skipped mode: reason}):
        the reason is "budget", or the exception type and message of a
        candidate whose build or compile failed (also warned about — a
        kernel the compiler refused must not pass for a slow one)."""
        block = build_kw.get("block", 128)
        cands = self._shortlist(g, key, heuristic, n_dev, block)
        B = min(key.batch, 128)   # bounded sample: bucket width, capped
        p = np.full((g.n, B), 1.0 / max(g.n, 1), np.float32)
        measured: dict[str, float] = {}
        engines: dict[str, object] = {}
        skipped: dict[str, str] = {}
        t0 = time.perf_counter()
        for mode in cands:
            if measured and time.perf_counter() - t0 > self.budget_s:
                skipped[mode] = "budget"
                continue
            try:
                eng = select_engine(g, batch=key.batch, mode=mode,
                                    **build_kw)
                x = eng.to_internal(jnp.asarray(p, eng.dtype))
                t = x
                acc = 0.5 * x
                measured[mode] = _time_round(eng, x, t, acc, self.reps)
                engines[mode] = eng
            except Exception as e:
                skipped[mode] = _describe(e)   # disqualified, with the cause
                warnings.warn(f"autotune candidate {mode!r} failed: "
                              f"{skipped[mode]}", RuntimeWarning,
                              stacklevel=2)
        return measured, engines, skipped


_DEFAULT_TUNER: Autotuner | None = None


def default_tuner() -> Autotuner:
    """Process-wide tuner over the default store path — what
    `select_engine(mode="tuned")` uses when no tuner is threaded in."""
    global _DEFAULT_TUNER
    if _DEFAULT_TUNER is None:
        _DEFAULT_TUNER = Autotuner()
    return _DEFAULT_TUNER
