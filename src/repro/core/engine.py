"""Pluggable SpMM engines: the solver hot path behind one interface.

Every PageRank solver iteration is one application of the transition matrix
P = A D^{-1} plus O(n) vector work. How P x is computed is a format choice,
not an algorithm choice, so it lives behind an `Engine`:

  * CooEngine          — gather + segment_sum over the COO edge list with the
                         1/deg[src] weights folded into a precomputed per-edge
                         array (no per-iteration inv_deg gather). The
                         universal fallback: works for any graph, any batch.
  * HubTailEngine      — degree-split layout for power-law graphs at scale:
                         vertices above a degree threshold (the hubs, which
                         on a scale-free graph receive the majority of all
                         edges) get dense fixed-width column panels reduced
                         by contiguous gather + row-sum, while the low-degree
                         tail stays on the COO/segment path. P is applied in
                         factored form (xd = x * inv_deg once per round), so
                         no per-edge weights are stored at all: ~4 bytes/edge
                         on the hub side vs COO's 12.
  * BlockEllEngine     — the block-ELL Pallas SpMM (`kernels/bsr_spmm`):
                         vertices BFS-reordered so edges cluster into BxB
                         tiles, each tile a dense matmul on the MXU. The
                         engine owns the perm/padding round-trip, so callers
                         see original vertex ids throughout.
  * FusedBlockEllEngine — BlockEllEngine whose Chebyshev round chains the
                         SpMM with the fused `cheb_step` kernel (one VMEM
                         pass for the recurrence + accumulation: 5nB bytes
                         per round instead of 8nB).
  * ShardedEngine      — the paper's Algorithm 1 vertex-set decomposition on
                         a device mesh (`core.distributed` shard_map bodies):
                         `Sharded1DEngine` owns a 1D row partition (all-gather
                         x per round, ~n floats/device), `Sharded2DEngine` an
                         (R, C) grid partition (psum_scatter + all_gather,
                         ~n/R + n/C floats/device). The engine owns the mesh,
                         the partition placement, and (2D) the nested column
                         layout, so callers see original vertex ids.

Engines are registered pytrees, so they pass through `jax.jit`/`lax.scan`
like the DeviceGraph they replace. Solvers call:

    x  = eng.to_internal(p)        # once per solve: layout in
    y  = eng.apply(x)              # per round: y = P x
    t, acc = eng.cheb_round(y, t, acc, ck)   # per round: vector work
    pi = eng.from_internal(acc)    # once per solve: layout out

Mass invariant (every engine honors it; the adaptive solver depends on it):
the internal layout is a permutation of the caller's vertices plus ZERO-mass
padding rows that stay zero through every `apply`/`cheb_round`, so column
sums and L1 norms computed directly on internal-layout arrays equal the
external ones. `cpaa_adaptive_fixed` exploits this to run its residual
control entirely inside the internal layout — one code path for COO,
block-ELL and the sharded engines, whose global (sharding-constrained)
arrays additionally make the residual reductions lower to cross-shard
psums for free.

Every engine also implements `refresh(g, delta, *, dg=None, ...)` — the
edge-update hook the serving registry calls instead of re-running
`select_engine` (format choice is sticky across updates). COO is free: the
registry patches the padded DeviceGraph in place and the engine, holding
the same object, is already current. Block-ELL re-tiles but reuses its BFS
perm when the delta's touched-vertex set is small (skipping the dominant
host-side BFS); the sharded engines rebuild their partition on the SAME
mesh. `delta` is a `graph.structure.EdgeDelta` (or None to force the
conservative rebuild).

`select_engine(g, batch)` picks a format host-side: with multiple devices
and a graph big enough to amortize the per-round collectives it shards
(2D grid when the mesh has >= 4 devices and n clears the 2D bar, 1D row
otherwise); on a single device it picks by tile fill-rate — block-ELL pays
off when the BxB tiles are dense enough that the dense-tile flops beat the
gather/scatter traffic of segment_sum (community and mesh-like graphs);
scattered graphs (kmer chains, power-law hubs) stay on COO.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import (put_partition_1d, put_partition_2d,
                                    spmv_1d_shard, spmv_2d_shard)
from repro.graph.ops import DeviceGraph, device_graph, spmm, spmv
from repro.graph.partition import (col_layout_perm, partition_1d,
                                   partition_2d)
from repro.graph.structure import (BlockEll, Graph, block_fill_rate,
                                   build_block_ell)
from repro.kernels.bsr_spmm.ops import bsr_spmm
from repro.kernels.cheb_step.ops import cheb_step

__all__ = [
    "CooEngine",
    "HubTailEngine",
    "BlockEllEngine",
    "FusedBlockEllEngine",
    "ShardedEngine",
    "Sharded1DEngine",
    "Sharded2DEngine",
    "as_engine",
    "heuristic_mode",
    "select_engine",
    "factor_grid",
    "ENGINE_MODES",
    "apply_counts",
    "apply_trace_log",
    "reset_apply_counts",
]

ENGINE_MODES = ("auto", "tuned", "coo", "hub_tail", "block_ell", "fused",
                "sharded_1d", "sharded_2d")

# Per-engine-class apply() invocation counts. apply() runs at TRACE time
# under jit, so in a jitted serving loop these count COMPILATIONS of the
# solve, not executions — which makes them a retrace detector: a warmed
# service holds them flat, and growth in steady state means jit cache
# misses (shape or pytree churn leaking into the hot path). In eager mode
# they count real SpMM executions. tests/ and the serve benches read them
# through `apply_counts()`.
APPLY_COUNTS: dict[str, int] = {}

# Trace-time signature log, one entry per apply() that ran under a tracer:
# (engine_name, "shape dtype" of the operand). Where APPLY_COUNTS says HOW
# MANY compilations happened, this log says WHAT each one saw — so the
# RetraceGate (repro.analysis.retrace) can print the offending signature
# diff instead of just "count went up". Eager applies are not logged.
APPLY_TRACE_LOG: list[tuple[str, str]] = []


def _count_apply(name: str, x: jax.Array | None = None) -> None:
    APPLY_COUNTS[name] = APPLY_COUNTS.get(name, 0) + 1
    if x is not None and isinstance(x, jax.core.Tracer):
        APPLY_TRACE_LOG.append((name, f"{x.shape} {x.dtype}"))


def apply_counts() -> dict[str, int]:
    """Copy of the per-engine apply() trace/execution counters."""
    return dict(APPLY_COUNTS)


def apply_trace_log() -> list[tuple[str, str]]:
    """Copy of the trace-time (engine, operand signature) event log."""
    return list(APPLY_TRACE_LOG)


def reset_apply_counts() -> None:
    APPLY_COUNTS.clear()
    APPLY_TRACE_LOG.clear()


def _default_cheb_round(y, t, acc, ck):
    """Unfused three-term recurrence + accumulation (XLA fuses the arithmetic;
    the kernel engines override this to fuse the HBM traffic too)."""
    t_next = 2.0 * y - t
    return t_next, acc + ck * t_next


@jax.tree_util.register_pytree_node_class
class CooEngine:
    """segment_sum over the COO edge list with precomputed edge weights."""

    name = "coo"

    def __init__(self, dg: DeviceGraph):
        self.dg = dg

    @property
    def n(self) -> int:
        return self.dg.n

    @property
    def dtype(self):
        return self.dg.inv_deg.dtype

    def to_internal(self, x: jax.Array) -> jax.Array:
        return x

    def from_internal(self, x: jax.Array) -> jax.Array:
        return x

    def apply(self, x: jax.Array) -> jax.Array:
        _count_apply("coo", x)
        return spmv(self.dg, x) if x.ndim == 1 else spmm(self.dg, x)

    def cheb_round(self, y, t, acc, ck):
        return _default_cheb_round(y, t, acc, ck)

    def refresh(self, g: Graph, delta=None, *, dg: DeviceGraph | None = None,
                **kw) -> "CooEngine":
        """Refresh after an edge-update batch (see the protocol note in the
        module docstring). The COO format needs no rebuild: when the caller
        patched this engine's own DeviceGraph in place (the incremental
        path) the engine is already current; a different dg (the rebuild
        fallback) just swaps in."""
        if dg is None:
            return CooEngine(device_graph(g, self.dtype))
        return self if dg is self.dg else CooEngine(dg)

    def tree_flatten(self):
        return (self.dg,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])


@jax.tree_util.register_pytree_node_class
class HubTailEngine:
    """Degree-split SpMM engine for skewed (power-law) graphs at scale.

    On a scale-free graph the hubs — the top few percent of vertices by
    degree — receive the majority of all edges, and segment_sum (a serial
    scatter-add on the CPU backend) pays per-edge for every one of them.
    The split sends each edge down the path its DESTINATION's degree earns:

      * hub rows (deg >= hub_min_deg, degree-sorted descending) are packed
        into fixed-width int32 column panels `panel_cols` [P, W]; a round
        gathers the panel columns contiguously, row-sums in the solve dtype,
        and reduces the per-panel partials with one tiny segment_sum over
        [P] -> [H] (P ~ hub_edges / W);
      * tail rows keep the proven gather + segment_sum over the remaining
        COO edges.

    P = A D^{-1} is applied in FACTORED form: xd = x * inv_deg is computed
    once per round (O(n)), so neither path stores per-edge weights — the
    hub side costs ~4 bytes/edge (one int32 column id) and the tail 8,
    against COO's 12 (f32 weights) or 10 (bf16). Panel padding slots hold
    the sentinel column id n, which indexes a zero row appended to xd
    inside `apply` — padding contributes exactly 0.0, preserving the mass
    invariant, and the internal layout itself is the identity (original
    vertex order, no padding rows).

    `weight_dtype` packs inv_deg (bf16 halves it; upcast to the solve dtype
    before the multiply, so accumulation stays full precision).
    """

    name = "hub_tail"
    DEFAULT_MIN_DEG = 32    # hub bar: deg >= 32 captures ~2/3 of the edges
    DEFAULT_PANEL_WIDTH = 32  # columns per panel: pad waste vs reduce count

    def __init__(self, inv_deg: jax.Array, tail_src: jax.Array,
                 tail_dst: jax.Array, panel_cols: jax.Array,
                 panel_hub: jax.Array, hub_ids: jax.Array, n_orig: int,
                 hub_min_deg: int, panel_width: int, acc_dtype=jnp.float32):
        self.inv_deg = inv_deg         # [n] weight_dtype (packed ok)
        self.tail_src = tail_src       # [m_tail] int32
        self.tail_dst = tail_dst       # [m_tail] int32
        self.panel_cols = panel_cols   # [P, W] int32, sentinel n = padding
        self.panel_hub = panel_hub     # [P] int32 hub rank of each panel
        self.hub_ids = hub_ids         # [H] int32 vertex id per hub rank
        self.n_orig = n_orig
        self.hub_min_deg = hub_min_deg
        self.panel_width = panel_width
        self.acc_dtype = jnp.dtype(acc_dtype)

    @classmethod
    def from_graph(cls, g: Graph, hub_min_deg: int | None = None,
                   panel_width: int | None = None, dtype=jnp.float32,
                   weight_dtype=None) -> "HubTailEngine":
        """Host-side build: degree-sort the hubs, lexsort their edges by
        (hub rank, src) for gather locality, pack into W-wide panels.
        All vectorized numpy — O(m log m)."""
        from repro.graph.ops import check_int32_range
        check_int32_range(g.n, g.m, what="HubTailEngine")
        thr = cls.DEFAULT_MIN_DEG if hub_min_deg is None else int(hub_min_deg)
        width = cls.DEFAULT_PANEL_WIDTH if panel_width is None \
            else int(panel_width)
        wdtype = jnp.dtype(dtype) if weight_dtype is None \
            else jnp.dtype(weight_dtype)
        n = g.n
        src = np.asarray(g.src)
        dst = np.asarray(g.dst)
        deg = np.bincount(src, minlength=n)
        inv_deg = 1.0 / np.maximum(deg, 1)

        hub_mask_v = deg >= thr
        hub_ids = np.flatnonzero(hub_mask_v)
        hub_ids = hub_ids[np.argsort(-deg[hub_ids],
                                     kind="stable")].astype(np.int32)
        H = int(hub_ids.size)
        hub_rank = np.full(n, -1, np.int64)
        hub_rank[hub_ids] = np.arange(H)
        is_hub = hub_mask_v[dst]
        hsrc = src[is_hub]
        hr = hub_rank[dst[is_hub]]
        tail_src = np.ascontiguousarray(src[~is_hub])
        tail_dst = np.ascontiguousarray(dst[~is_hub])
        order = np.lexsort((hsrc, hr))
        hsrc, hr = hsrc[order], hr[order]
        hdeg = np.bincount(hr, minlength=H)
        panels_per_hub = np.maximum((hdeg + width - 1) // width, 1)
        n_panels = int(panels_per_hub.sum())
        cols = np.full((n_panels, width), n, np.int32)  # n -> zero sentinel
        panel_hub = np.repeat(np.arange(H, dtype=np.int32), panels_per_hub)
        panel_base = np.concatenate([[0], np.cumsum(panels_per_hub)[:-1]])
        starts = np.concatenate([[0], np.cumsum(hdeg)[:-1]])
        pos = panel_base[hr] * width + (np.arange(hsrc.size) - starts[hr])
        cols.ravel()[pos] = hsrc
        return cls(inv_deg=jnp.asarray(inv_deg, wdtype),
                   tail_src=jnp.asarray(tail_src),
                   tail_dst=jnp.asarray(tail_dst),
                   panel_cols=jnp.asarray(cols),
                   panel_hub=jnp.asarray(panel_hub),
                   hub_ids=jnp.asarray(hub_ids),
                   n_orig=n, hub_min_deg=thr, panel_width=width,
                   acc_dtype=dtype)

    @property
    def n(self) -> int:
        return self.n_orig

    @property
    def n_hubs(self) -> int:
        return self.hub_ids.shape[0]

    @property
    def dtype(self):
        # the SOLVE dtype, not the packed weight storage dtype: solvers
        # build their p / carry vectors from this, and those must stay at
        # accumulation precision even when inv_deg is bf16
        return self.acc_dtype

    @property
    def weight_dtype(self):
        return self.inv_deg.dtype

    def to_internal(self, x: jax.Array) -> jax.Array:
        return x

    def from_internal(self, x: jax.Array) -> jax.Array:
        return x

    def apply(self, x: jax.Array) -> jax.Array:
        _count_apply(self.name, x)
        inv = self.inv_deg
        if inv.dtype != x.dtype:
            inv = inv.astype(x.dtype)   # packed storage -> full-precision mul
        xd = x * (inv if x.ndim == 1 else inv[:, None])
        # sentinel row n: panel padding gathers exactly 0.0
        zero = jnp.zeros((1,) + x.shape[1:], xd.dtype)
        xd = jnp.concatenate([xd, zero])
        y = jax.ops.segment_sum(xd[self.tail_src], self.tail_dst,
                                num_segments=self.n_orig)
        part = xd[self.panel_cols].sum(axis=1)
        hub_y = jax.ops.segment_sum(part, self.panel_hub,
                                    num_segments=self.n_hubs)
        return y.at[self.hub_ids].add(hub_y)

    def cheb_round(self, y, t, acc, ck):
        return _default_cheb_round(y, t, acc, ck)

    def refresh(self, g: Graph, delta=None, *, dg=None,
                **kw) -> "HubTailEngine":
        """Rebuild for the updated graph with the same split knobs. An edge
        delta can move vertices across the hub threshold, so the honest
        refresh is a full (vectorized, host-side) rebuild — no incremental
        patch path; the registry's padded DeviceGraph, if any, is not
        consulted."""
        return type(self).from_graph(g, hub_min_deg=self.hub_min_deg,
                                     panel_width=self.panel_width,
                                     dtype=self.acc_dtype,
                                     weight_dtype=self.inv_deg.dtype)

    def tree_flatten(self):
        children = (self.inv_deg, self.tail_src, self.tail_dst,
                    self.panel_cols, self.panel_hub, self.hub_ids)
        aux = (self.n_orig, self.hub_min_deg, self.panel_width,
               str(self.acc_dtype))
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        n_orig, hub_min_deg, panel_width, acc_dtype = aux
        return cls(*children, n_orig=n_orig, hub_min_deg=hub_min_deg,
                   panel_width=panel_width, acc_dtype=jnp.dtype(acc_dtype))


@jax.tree_util.register_pytree_node_class
class BlockEllEngine:
    """Block-ELL SpMM engine over BFS-reordered, block-padded vertices.

    Internal layout: [n_pad] (or [n_pad, B]) float32 in BFS order, where
    n_pad = n_row_blocks * block >= n. Padding rows carry zero mass and stay
    zero through every round (empty slots have all-zero values), so
    `from_internal` is a plain inverse-permutation gather of the first rows.
    """

    name = "block_ell"

    # jaxlint: disable=JL004 -- fill_rate is an informational build statistic, deliberately not pytree state
    def __init__(self, block_cols: jax.Array, values: jax.Array,
                 perm: jax.Array, inv_perm: jax.Array, n_orig: int,
                 block: int, use_kernel: bool | None = None,
                 interpret: bool | None = None, fill_rate: float | None = None):
        self.block_cols = block_cols   # [n_rb, S] int32
        self.values = values           # [n_rb, S, B, B] f32
        self.perm = perm               # [n_orig] old id at BFS position
        self.inv_perm = inv_perm       # [n_orig] BFS position of old id
        self.n_orig = n_orig
        self.block = block
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.fill_rate = fill_rate     # informational; not a pytree aux

    # stable-shape slot padding rounds S up to a multiple of this. The
    # values tensor is the engine's dominant allocation (4.3 GB at S=8 on
    # the 1.04e6-vertex mesh), so a power-of-two step (one extra tile:
    # 8 -> 16) would double it and outgrow a 16 GB chip next to the solve's
    # working set; a step of 4 adds at most 3 slots per growth.
    SLOT_QUANTUM = 4

    @classmethod
    def from_block_ell(cls, be: BlockEll, use_kernel: bool | None = None,
                       interpret: bool | None = None,
                       pad_slots: bool = False) -> "BlockEllEngine":
        block_cols, values = be.block_cols, be.values
        if pad_slots:
            q = cls.SLOT_QUANTUM
            s = -(-be.slots // q) * q
            if s > be.slots:
                # extra slots point at the diagonal with zero values: harmless
                # by construction, and the padded S keeps jit shapes stable
                # when edge updates change the true max-slots-per-row-block.
                n_rb = be.n_row_blocks
                diag = np.tile(np.arange(n_rb, dtype=np.int32)[:, None],
                               (1, s - be.slots))
                block_cols = np.concatenate([block_cols, diag], axis=1)
                values = np.concatenate(
                    [values, np.zeros((n_rb, s - be.slots, be.block, be.block),
                                      np.float32)], axis=1)
        inv = np.empty(be.n_orig, np.int64)
        inv[be.perm] = np.arange(be.n_orig)
        return cls(block_cols=jnp.asarray(block_cols),
                   values=jnp.asarray(values),
                   perm=jnp.asarray(be.perm, jnp.int32),
                   inv_perm=jnp.asarray(inv, jnp.int32),
                   n_orig=be.n_orig, block=be.block,
                   use_kernel=use_kernel, interpret=interpret,
                   fill_rate=be.fill_rate)

    @classmethod
    def from_graph(cls, g: Graph, block: int = 128, reorder: bool = True,
                   use_kernel: bool | None = None,
                   interpret: bool | None = None,
                   pad_slots: bool = False,
                   perm=None) -> "BlockEllEngine":
        return cls.from_block_ell(build_block_ell(g, block=block,
                                                  reorder=reorder, perm=perm),
                                  use_kernel=use_kernel, interpret=interpret,
                                  pad_slots=pad_slots)

    @property
    def n(self) -> int:
        return self.n_orig

    @property
    def n_pad(self) -> int:
        return self.block_cols.shape[0] * self.block

    @property
    def dtype(self):
        return self.values.dtype

    def to_internal(self, x: jax.Array) -> jax.Array:
        xp = x.astype(jnp.float32)[self.perm]
        pad = self.n_pad - self.n_orig
        if pad:
            xp = jnp.pad(xp, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return xp

    def from_internal(self, x: jax.Array) -> jax.Array:
        return x[self.inv_perm]

    def apply(self, x: jax.Array) -> jax.Array:
        _count_apply(self.name, x)
        return bsr_spmm(self.block_cols, self.values, x,
                        use_kernel=self.use_kernel, interpret=self.interpret)

    def cheb_round(self, y, t, acc, ck):
        return _default_cheb_round(y, t, acc, ck)

    # a localized delta barely moves tile fill, so the cached BFS perm stays
    # good and the rebuild skips the (host python, by far dominant) BFS;
    # past this touched fraction the locality argument is gone -> re-BFS
    REFRESH_PERM_MAX_TOUCHED = 0.25

    def refresh(self, g: Graph, delta=None, *,
                dg: DeviceGraph | None = None, stable_shapes: bool = True,
                **kw):
        """Rebuild the tiles for the updated graph. When the delta's
        touched-vertex set is a small fraction of the graph the existing
        BFS perm is reused (any perm is valid — only fill-rate is at
        stake), which turns the rebuild into one vectorized re-tiling pass;
        a delocalized delta (or none) re-runs the BFS."""
        perm = None
        if delta is not None and \
                delta.touched.size <= self.REFRESH_PERM_MAX_TOUCHED * g.n:
            perm = np.asarray(self.perm, np.int64)
        return type(self).from_graph(g, block=self.block,
                                     use_kernel=self.use_kernel,
                                     interpret=self.interpret,
                                     pad_slots=stable_shapes,
                                     perm=perm)

    def tree_flatten(self):
        children = (self.block_cols, self.values, self.perm, self.inv_perm)
        aux = (self.n_orig, self.block, self.use_kernel, self.interpret)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
class FusedBlockEllEngine(BlockEllEngine):
    """Block-ELL SpMM + fused Chebyshev-update kernel in the scan body."""

    name = "block_ell_fused"

    def cheb_round(self, y, t, acc, ck):
        return cheb_step(y, t, acc, ck,
                         use_kernel=self.use_kernel, interpret=self.interpret)


def _take_devices(n_dev: int | None):
    devs = jax.devices()
    if n_dev is None:
        return devs
    if n_dev > len(devs):
        raise ValueError(f"asked for {n_dev} devices, only {len(devs)} exist")
    return devs[:n_dev]


def factor_grid(n_dev: int) -> tuple[int, int]:
    """Most-square (R, C) with R * C == n_dev and R <= C (wider column axis
    keeps the all-gathered sub-chunks small): 8 -> (2, 4), 16 -> (4, 4)."""
    r = int(math.isqrt(n_dev))
    while n_dev % r:
        r -= 1
    return (r, n_dev // r)


class ShardedEngine:
    """Shared surface of the mesh-sharded engines (see module docstring).

    Both variants keep the solve vectors GLOBAL jax arrays carrying a
    sharding constraint; only `apply` drops into shard_map (the
    `core.distributed` shard-local SpMV bodies), so the Chebyshev recurrence
    and normalization in `cpaa_fixed` run unchanged on sharded carries and
    XLA partitions the O(n) vector work across the mesh for free.
    """

    def cheb_round(self, y, t, acc, ck):
        return _default_cheb_round(y, t, acc, ck)

    @property
    def n(self) -> int:
        return self.n_orig

    @property
    def dtype(self):
        return self.weight.dtype

    def _constrain(self, x: jax.Array, spec) -> jax.Array:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


@jax.tree_util.register_pytree_node_class
class Sharded1DEngine(ShardedEngine):
    """Row-partitioned CPAA engine (the paper-faithful decomposition).

    Device d owns every edge whose dst falls in row-chunk d; each round
    all-gathers x (~n floats per device) and computes its local rows.
    Internal layout: the original vertex order, zero-padded to
    n_pad = rows_per_dev * n_dev and sharded over all mesh axes on dim 0.
    """

    name = "sharded_1d"

    def __init__(self, mesh: Mesh, axes, src: jax.Array, dst_local: jax.Array,
                 weight: jax.Array, n_orig: int, n_pad: int,
                 rows_per_dev: int, comm_dtype=None):
        self.mesh = mesh
        self.axes = axes if isinstance(axes, str) else tuple(axes)
        self.src = src                 # [D, E] int32, global src ids
        self.dst_local = dst_local     # [D, E] int32, chunk-local dst
        self.weight = weight           # [D, E] f32, 1/deg[src] (0 on padding)
        self.n_orig = n_orig
        self.n_pad = n_pad
        self.rows_per_dev = rows_per_dev
        self.comm_dtype = None if comm_dtype is None else jnp.dtype(comm_dtype)

    @classmethod
    def from_graph(cls, g: Graph, mesh: Mesh | None = None,
                   n_dev: int | None = None, lane: int = 128,
                   dtype=jnp.float32, comm_dtype=None) -> "Sharded1DEngine":
        if mesh is None:
            devs = _take_devices(n_dev)
            mesh = Mesh(np.asarray(devs), ("dev",))
        axes = tuple(mesh.axis_names)
        part = partition_1d(g, int(mesh.devices.size), lane=lane)
        src, dst_local, weight = put_partition_1d(part, mesh, axes)
        if weight.dtype != jnp.dtype(dtype):
            weight = weight.astype(dtype)
        return cls(mesh=mesh, axes=axes, src=src, dst_local=dst_local,
                   weight=weight, n_orig=g.n, n_pad=part.n,
                   rows_per_dev=part.rows_per_dev, comm_dtype=comm_dtype)

    def _vec_spec(self, ndim: int):
        return P(self.axes, *([None] * (ndim - 1)))

    def to_internal(self, x: jax.Array) -> jax.Array:
        pad = self.n_pad - x.shape[0]
        if pad:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return self._constrain(x, self._vec_spec(x.ndim))

    def from_internal(self, x: jax.Array) -> jax.Array:
        return x[: self.n_orig] if self.n_orig != self.n_pad else x

    def apply(self, x: jax.Array) -> jax.Array:
        _count_apply(self.name, x)
        vec_spec = self._vec_spec(x.ndim)
        edge_spec = P(self.axes)

        def body(x_sh, src, dst_local, weight):
            return spmv_1d_shard(x_sh, src, dst_local, weight,
                                 axis_name=self.axes, rows=self.rows_per_dev,
                                 comm_dtype=self.comm_dtype)

        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(vec_spec, edge_spec, edge_spec, edge_spec),
            out_specs=vec_spec)
        return fn(x, self.src, self.dst_local, self.weight)

    def refresh(self, g: Graph, delta=None, *, dg: DeviceGraph | None = None,
                lane: int = 128, **kw) -> "Sharded1DEngine":
        """Rebuild the row partition for the updated graph on the SAME mesh
        (device placement and axis names kept, so recompiled-solve churn is
        limited to genuinely changed shapes)."""
        return type(self).from_graph(g, mesh=self.mesh, lane=lane,
                                     dtype=self.weight.dtype,
                                     comm_dtype=self.comm_dtype)

    def tree_flatten(self):
        children = (self.src, self.dst_local, self.weight)
        aux = (self.mesh, self.axes, self.n_orig, self.n_pad,
               self.rows_per_dev, self.comm_dtype)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        mesh, axes, n_orig, n_pad, rows_per_dev, comm_dtype = aux
        return cls(mesh, axes, *children, n_orig=n_orig, n_pad=n_pad,
                   rows_per_dev=rows_per_dev, comm_dtype=comm_dtype)


@jax.tree_util.register_pytree_node_class
class Sharded2DEngine(ShardedEngine):
    """Grid-partitioned CPAA engine (beyond-paper collective optimization).

    Device (r, c) owns edges with dst in row-chunk r and src in nested
    column group c; per round the partial row results are reduce-scattered
    along the grid row (~n/R floats) and the new column chunks all-gathered
    down the grid column (~n/C floats) — see `graph.partition.Partition2D`.

    Internal layout: the nested COLUMN layout, `padded(x)[perm]` with
    perm = col_layout_perm(n_pad, grid), sharded P(col_axis) on dim 0
    (replicated over the row axis). With perm=None (the historical
    `cpaa_distributed_2d` convention) callers pass and receive column-layout
    vectors themselves and to/from_internal only constrain the sharding.
    """

    name = "sharded_2d"

    def __init__(self, mesh: Mesh, row_axis, col_axis: str,
                 src_local: jax.Array, dst_local: jax.Array,
                 weight: jax.Array, perm: jax.Array | None,
                 inv_perm: jax.Array | None, n_orig: int, n_pad: int,
                 rows_per_chunk: int, comm_dtype=None):
        self.mesh = mesh
        self.row_axis = row_axis if isinstance(row_axis, str) \
            else tuple(row_axis)
        self.col_axis = col_axis
        self.src_local = src_local     # [R, C, E] int32 (col-chunk-local src)
        self.dst_local = dst_local     # [R, C, E] int32 (row-chunk-local dst)
        self.weight = weight           # [R, C, E] f32
        self.perm = perm               # [n_pad] column-layout gather, or None
        self.inv_perm = inv_perm       # [n_pad] inverse gather, or None
        self.n_orig = n_orig
        self.n_pad = n_pad
        self.rows_per_chunk = rows_per_chunk
        self.comm_dtype = None if comm_dtype is None else jnp.dtype(comm_dtype)

    @classmethod
    def from_graph(cls, g: Graph, mesh: Mesh | None = None,
                   grid: tuple[int, int] | None = None, lane: int = 128,
                   dtype=jnp.float32, comm_dtype=None) -> "Sharded2DEngine":
        if mesh is None:
            if grid is None:
                grid = factor_grid(len(jax.devices()))
            r, c = grid
            devs = _take_devices(r * c)
            mesh = Mesh(np.asarray(devs).reshape(r, c), ("row", "col"))
            row_axis, col_axis = "row", "col"
        else:
            names = tuple(mesh.axis_names)
            row_axis = names[0] if len(names) == 2 else names[:-1]
            col_axis = names[-1]
            if grid is None:
                c = mesh.shape[col_axis]
                grid = (int(mesh.devices.size) // c, c)
        part = partition_2d(g, grid, lane=lane)
        src_local, dst_local, weight = put_partition_2d(part, mesh, row_axis,
                                                        col_axis)
        if weight.dtype != jnp.dtype(dtype):
            weight = weight.astype(dtype)
        perm = col_layout_perm(part.n, grid)
        inv_perm = np.argsort(perm)
        return cls(mesh=mesh, row_axis=row_axis, col_axis=col_axis,
                   src_local=src_local, dst_local=dst_local, weight=weight,
                   perm=jnp.asarray(perm, jnp.int32),
                   inv_perm=jnp.asarray(inv_perm, jnp.int32),
                   n_orig=g.n, n_pad=part.n,
                   rows_per_chunk=part.rows_per_chunk, comm_dtype=comm_dtype)

    def _vec_spec(self, ndim: int):
        return P(self.col_axis, *([None] * (ndim - 1)))

    def to_internal(self, x: jax.Array) -> jax.Array:
        if self.perm is not None:
            pad = self.n_pad - x.shape[0]
            if pad:
                x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            x = x[self.perm]
        return self._constrain(x, self._vec_spec(x.ndim))

    def from_internal(self, x: jax.Array) -> jax.Array:
        if self.inv_perm is None:
            return x
        return x[self.inv_perm][: self.n_orig]

    def apply(self, x: jax.Array) -> jax.Array:
        _count_apply(self.name, x)
        vec_spec = self._vec_spec(x.ndim)
        edge_spec = P(self.row_axis, self.col_axis)

        def body(x_col, src_local, dst_local, weight):
            return spmv_2d_shard(x_col, src_local, dst_local, weight,
                                 row_axis=self.row_axis,
                                 col_axis=self.col_axis,
                                 rows=self.rows_per_chunk,
                                 comm_dtype=self.comm_dtype)

        # check_vma=False: the output IS replicated over the row axis by
        # construction (the final all_gather along it makes every row group
        # identical), but the varying-axis type system can't prove that
        # through psum_scatter.
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(vec_spec, edge_spec, edge_spec, edge_spec),
            out_specs=vec_spec, check_vma=False)
        return fn(x, self.src_local, self.dst_local, self.weight)

    def refresh(self, g: Graph, delta=None, *, dg: DeviceGraph | None = None,
                lane: int = 128, **kw) -> "Sharded2DEngine":
        """Rebuild the grid partition for the updated graph on the SAME
        mesh (grid shape and device placement kept)."""
        return type(self).from_graph(g, mesh=self.mesh, lane=lane,
                                     dtype=self.weight.dtype,
                                     comm_dtype=self.comm_dtype)

    def tree_flatten(self):
        children = (self.src_local, self.dst_local, self.weight,
                    self.perm, self.inv_perm)
        aux = (self.mesh, self.row_axis, self.col_axis, self.n_orig,
               self.n_pad, self.rows_per_chunk, self.comm_dtype)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        mesh, row_axis, col_axis, n_orig, n_pad, rows, comm_dtype = aux
        return cls(mesh, row_axis, col_axis, *children, n_orig=n_orig,
                   n_pad=n_pad, rows_per_chunk=rows, comm_dtype=comm_dtype)


def as_engine(obj) -> CooEngine | BlockEllEngine:
    """Coerce a DeviceGraph (the historical solver argument) to an engine;
    pass engines through unchanged."""
    if isinstance(obj, DeviceGraph):
        return CooEngine(obj)
    if hasattr(obj, "apply") and hasattr(obj, "to_internal"):
        return obj
    raise TypeError(f"expected DeviceGraph or Engine, got {type(obj)!r}")


def _default_min_fill() -> float:
    # On the MXU, dense-tile flops are nearly free next to gather/scatter
    # HBM traffic, so even thin tiles pay off; on CPU the jnp-oracle einsum
    # spends real flops on zero fill, so the bar is higher (measured
    # crossover on mesh graphs is ~0.03-0.05 at B=128).
    return 0.01 if jax.default_backend() == "tpu" else 0.05


# auto mode only shards graphs at least this large: below it one device's
# SpMV is faster than any per-round collective (docs/performance.md has the
# n vs n/R + n/C volume model the 4x multiplier for the 2D bar comes from).
SHARDED_MIN_N = 1 << 16

# auto mode considers the hub/tail split only past this size (below it COO's
# segment_sum is already cheap and the split buys layout complexity for
# nothing) and only when hubs at the default threshold receive at least this
# fraction of all edges (degree-skew bar: power-law graphs clear it easily —
# ~2/3 at the chung-lu operating point — while meshes/grids, whose max degree
# sits under the threshold, score 0.0 and keep their fill-rate choice).
HUB_TAIL_MIN_N = 1 << 17
HUB_TAIL_MIN_EDGE_FRAC = 0.4


def _hub_edge_fraction(g: Graph, thr: int) -> float:
    """Fraction of directed edges whose destination has deg >= thr."""
    deg = g.deg
    m = max(int(g.m), 1)
    return float(deg[deg >= thr].sum()) / m


def _auto_choice(g: Graph, batch: int | None = None, *, block: int = 128,
                 min_fill: float | None = None, mesh: Mesh | None = None,
                 sharded_min_n: int | None = None, probe_cache=None):
    """The zero-cost heuristic's decision, WITHOUT building anything:
    (mode, perm), where perm is the BFS permutation when the fill probe ran
    fresh (the block-ELL build reuses it; None on a probe-cache hit or a
    non-tiled pick). `probe_cache` is any object with
    get_fill(g, block) / put_fill(g, block, fill) — see core.autotune."""
    # multi-device: shard when the graph is large enough that the
    # per-device row work dominates the per-round collective (1D moves ~n
    # floats/device/round; 2D ~n/R + n/C, but needs a still-larger n to
    # amortize its two collective phases and grid padding).
    n_dev = int(mesh.devices.size) if mesh is not None else jax.device_count()
    thr = SHARDED_MIN_N if sharded_min_n is None else sharded_min_n
    if n_dev >= 2 and g.n >= thr:
        if n_dev >= 4 and g.n >= 4 * thr and \
                (mesh is None or len(mesh.axis_names) >= 2):
            return "sharded_2d", None
        return "sharded_1d", None

    # single device, paper-scale skew: when the hubs carry most of the
    # edge mass the degree split beats any uniform layout (and the fill-rate
    # probe below — a host BFS + tile count — is exactly what we'd rather
    # not run on a 10^7-edge scattered graph)
    if g.n >= HUB_TAIL_MIN_N and \
            _hub_edge_fraction(g, HubTailEngine.DEFAULT_MIN_DEG) >= \
            HUB_TAIL_MIN_EDGE_FRAC:
        return "hub_tail", None

    # too small to tile -> COO without paying the host-side build
    if g.n < 2 * block or (batch is not None and batch < 8 and g.n < 8 * block):
        return "coo", None
    # probe the tiling fill WITHOUT materializing tile values — scattered
    # graphs (the ones that fail the threshold) are exactly where the
    # [n_rb, S, B, B] tensor would be largest, and this runs on every
    # serving epoch bump; the probe cache remembers the fill per (graph
    # fingerprint, block) so re-probes of an already-seen shape skip the
    # host BFS + tile census entirely
    fill = perm = None
    if probe_cache is not None:
        fill = probe_cache.get_fill(g, block)
    if fill is None:
        fill, perm = block_fill_rate(g, block=block)
        if probe_cache is not None:
            probe_cache.put_fill(g, block, fill)
    threshold = _default_min_fill() if min_fill is None else min_fill
    if fill < threshold:
        return "coo", None
    return "fused", perm


def heuristic_mode(g: Graph, batch: int | None = None, *, block: int = 128,
                   min_fill: float | None = None, mesh: Mesh | None = None,
                   sharded_min_n: int | None = None, probe_cache=None) -> str:
    """What `select_engine(mode="auto")` would pick for (g, batch), as a
    concrete mode string, without building the engine — the zero-cost tier
    the autotuner measures against (and ties back toward)."""
    return _auto_choice(g, batch, block=block, min_fill=min_fill, mesh=mesh,
                        sharded_min_n=sharded_min_n,
                        probe_cache=probe_cache)[0]


def select_engine(g: Graph, batch: int | None = None, mode: str = "auto", *,
                  dg: DeviceGraph | None = None, dtype=jnp.float32,
                  block: int = 128, min_fill: float | None = None,
                  use_kernel: bool | None = None, interpret: bool | None = None,
                  stable_shapes: bool = False, mesh: Mesh | None = None,
                  grid: tuple[int, int] | None = None, lane: int = 128,
                  comm_dtype=None, sharded_min_n: int | None = None,
                  weight_dtype=None, tuner=None, probe_cache=None):
    """Pick/build the solve engine for a graph (host-side, once per epoch).

    mode: "coo" | "hub_tail" | "block_ell" | "fused" | "sharded_1d" |
    "sharded_2d" force a format (dashes accepted: "hub-tail"); "auto" first
    checks the device axis — with >= 2 devices and g.n >= `sharded_min_n` it
    shards (a 2D grid when >= 4 devices and the graph is big enough to
    amortize the two-phase collectives, the paper-faithful 1D rows
    otherwise) — then, on a single device, large skewed graphs (n >=
    HUB_TAIL_MIN_N and hubs receiving >= HUB_TAIL_MIN_EDGE_FRAC of the
    edges) take the hub/tail split, and everything else falls to the
    fill-rate choice: block-ELL when its tile fill-rate clears `min_fill`
    (dense-enough tiles to beat segment_sum), otherwise COO. "tuned"
    replaces the guess with a measurement: the workload-bucketed autotuner
    (core.autotune) consults its persistent store and, on a miss, times
    the feasible candidates and picks the measured winner (tie-break
    toward the heuristic's choice).
    batch: expected personalization width (auto mode nudges tiny batches on
    small graphs back to COO; the MXU win needs columns to amortize the
    tiling round-trip).
    dg: reuse an existing DeviceGraph for the COO path (the serving registry
    passes its padded, shape-stable device graph).
    stable_shapes: pad the ELL slot count to a multiple of
    `BlockEllEngine.SLOT_QUANTUM` so edge updates rarely change jit shapes.
    mesh / grid / lane / comm_dtype: sharded-engine knobs — an explicit mesh
    to run on (default: all devices), the (R, C) grid for sharded_2d, the
    partition padding lane, and an optional wire dtype for the all-gather.
    weight_dtype: packed storage dtype for edge weights / inv_deg on the
    COO and hub-tail paths (bf16 halves them; accumulation stays in
    `dtype`). The tile/partition engines ignore it (f32 values).
    tuner: the `core.autotune.Autotuner` mode="tuned" consults (None = the
    process-wide default over `$REPRO_TUNE_CACHE`).
    probe_cache: fill-probe cache for auto mode (get_fill/put_fill; None =
    probe every call — the serving registry threads the process cache).
    """
    mode = mode.replace("-", "_")
    if mode not in ENGINE_MODES:
        raise ValueError(f"engine mode {mode!r} not in {ENGINE_MODES}")

    def coo():
        return CooEngine(dg if dg is not None
                         else device_graph(g, dtype,
                                           weight_dtype=weight_dtype))

    def hub_tail():
        return HubTailEngine.from_graph(g, dtype=dtype,
                                        weight_dtype=weight_dtype)

    perm = None
    if mode == "tuned":
        from repro.core.autotune import default_tuner  # lazy: no cycle
        t = default_tuner() if tuner is None else tuner
        dec = t.tune(g, batch=batch, dg=dg, dtype=dtype, block=block,
                     min_fill=min_fill, use_kernel=use_kernel,
                     interpret=interpret, stable_shapes=stable_shapes,
                     mesh=mesh, grid=grid, lane=lane, comm_dtype=comm_dtype,
                     sharded_min_n=sharded_min_n, weight_dtype=weight_dtype)
        if dec.engine is not None:   # freshly measured winner: reuse as-is
            return dec.engine
        mode = dec.mode
    if mode == "auto":
        mode, perm = _auto_choice(g, batch, block=block, min_fill=min_fill,
                                  mesh=mesh, sharded_min_n=sharded_min_n,
                                  probe_cache=probe_cache)

    if mode == "coo":
        return coo()
    if mode == "hub_tail":
        return hub_tail()
    if mode in ("block_ell", "fused"):
        cls = BlockEllEngine if mode == "block_ell" else FusedBlockEllEngine
        return cls.from_graph(g, block=block, use_kernel=use_kernel,
                              interpret=interpret,
                              pad_slots=stable_shapes, perm=perm)
    if mode == "sharded_1d":
        return Sharded1DEngine.from_graph(g, mesh=mesh, lane=lane,
                                          dtype=dtype, comm_dtype=comm_dtype)
    return Sharded2DEngine.from_graph(g, mesh=mesh, grid=grid, lane=lane,
                                      dtype=dtype, comm_dtype=comm_dtype)
