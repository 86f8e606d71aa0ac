"""JAX message-passing primitives shared by PageRank and the GNN zoo.

JAX has no CSR SpMV; the idiomatic TPU-compatible formulation is
gather + segment_sum over the COO edge list. These functions are pure and
jit-friendly; device arrays in, device arrays out.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph

__all__ = [
    "DeviceGraph",
    "device_graph",
    "check_int32_range",
    "EdgeSlots",
    "SlotPatch",
    "patch_device_graph",
    "spmv",
    "spmm",
    "aggregate",
    "edge_softmax",
    "degree_normalize",
]


class DeviceGraph:
    """Device-resident COO graph + precomputed 1/deg (the paper's P).

    `w` is the [m] per-edge weight of P: 1/deg[src] for real edges, 0 for
    the zero-weight padding edges the serving registry appends to keep jit
    shapes stable across updates. `device_graph` always precomputes it, so
    the per-iteration SpMV is one gather + one multiply + one segment_sum —
    no inv_deg gather on the hot path. Hand-built graphs may pass w=None and
    fall back to gathering inv_deg[src] per call.

    `inv_deg` stays for vertex-wise consumers (degree_normalize, GNN
    normalizations).
    """

    def __init__(self, n: int, src: jax.Array, dst: jax.Array,
                 inv_deg: jax.Array, w: jax.Array | None = None):
        self.n = n
        self.src = src
        self.dst = dst
        self.inv_deg = inv_deg
        self.w = w
        self._csr = None

    def csr(self):
        """Sorted-src CSR view (deg, row_start, dst_sorted) as device arrays,
        computed host-side once and cached on the instance. Zero-weight
        padding edges are excluded so sampling never walks them. Call outside
        jit (the result feeds jitted code as plain arguments)."""
        if self._csr is None:
            # jaxlint: disable=JL001 -- documented one-time host CSR build
            src = np.asarray(self.src)
            dst = np.asarray(self.dst)  # jaxlint: disable=JL001 -- same host build
            if self.w is not None:
                # jaxlint: disable=JL001 -- padding filter needs concrete w once
                keep = np.asarray(self.w) > 0
                src, dst = src[keep], dst[keep]
            deg = np.bincount(src, minlength=self.n).astype(np.int32)
            row_start = np.concatenate(
                [np.zeros(1, np.int32), np.cumsum(deg, dtype=np.int32)[:-1]])
            order = np.argsort(src, kind="stable")
            self._csr = (jnp.asarray(deg), jnp.asarray(row_start),
                         jnp.asarray(dst[order]))
        return self._csr

    def tree_flatten(self):
        return (self.src, self.dst, self.inv_deg, self.w), self.n

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux, *children)


jax.tree_util.register_pytree_node(
    DeviceGraph, DeviceGraph.tree_flatten, DeviceGraph.tree_unflatten)


_INT32_MAX = np.iinfo(np.int32).max


def check_int32_range(n: int, nnz: int, what: str = "graph") -> None:
    """Fail loudly where an index would silently wrap in the int32 edge
    arrays. Every device-side index (vertex ids, slot ids, segment ids) is
    int32; past 2^31-1 a build would produce negative indices and scatter
    mass to garbage rows with no error."""
    if n > _INT32_MAX:
        raise ValueError(
            f"{what}: n={n} exceeds int32 range ({_INT32_MAX}); "
            "vertex ids are stored as int32 device arrays")
    if nnz > _INT32_MAX:
        raise ValueError(
            f"{what}: nnz={nnz} exceeds int32 range ({_INT32_MAX}); "
            "edge slot ids are stored as int32 device arrays")


def _chunked_device_1d(arr: np.ndarray, dtype, chunk: int) -> jax.Array:
    """Host->device transfer of a 1D array in bounded chunks: each chunk is
    converted + transferred on its own, then concatenated ON DEVICE, so the
    peak extra host allocation is O(chunk) instead of O(m) (the float64 ->
    storage-dtype conversion is where a one-shot transfer doubles peak host
    memory at 10^7+ edges)."""
    if arr.shape[0] <= chunk:
        return jnp.asarray(arr, dtype)
    parts = [jnp.asarray(arr[s:s + chunk], dtype)
             for s in range(0, arr.shape[0], chunk)]
    return jnp.concatenate(parts)


def device_graph(g: Graph, dtype=jnp.float32,
                 pad_edges_to: int | None = None,
                 weight_dtype=None,
                 chunk_edges: int | None = None) -> DeviceGraph:
    """Build the device-resident COO graph.

    weight_dtype: storage dtype for the folded per-edge weights `w` (and
    only them — inv_deg stays in `dtype` for its vertex-wise consumers).
    Defaults to `dtype`; jnp.bfloat16 halves the weight array and the SpMV
    upcasts to the solve dtype at multiply time (f32 accumulation), bounding
    the parity cost to the one rounding of 1/deg.
    chunk_edges: transfer the edge arrays to device in chunks of this many
    edges (see `_chunked_device_1d`); None = one shot.
    """
    check_int32_range(g.n, g.m if pad_edges_to is None else pad_edges_to,
                      what="device_graph")
    wdtype = jnp.dtype(dtype) if weight_dtype is None else \
        jnp.dtype(weight_dtype)
    # jaxlint: disable=JL003 -- exact host 1/deg before the device-dtype cast
    deg = np.maximum(g.deg, 1).astype(np.float64)
    inv_deg = 1.0 / deg
    src, dst, w = g.src, g.dst, inv_deg[g.src]
    if pad_edges_to is not None and pad_edges_to > g.m:
        pad = pad_edges_to - g.m
        zeros = np.zeros(pad, np.int32)
        src = np.concatenate([src, zeros])
        dst = np.concatenate([dst, zeros])
        w = np.concatenate([w, np.zeros(pad)])
    if chunk_edges is not None and chunk_edges > 0:
        jsrc = _chunked_device_1d(src, jnp.int32, chunk_edges)
        jdst = _chunked_device_1d(dst, jnp.int32, chunk_edges)
        jw = _chunked_device_1d(w, wdtype, chunk_edges)
    else:
        jsrc, jdst, jw = (jnp.asarray(src), jnp.asarray(dst),
                          jnp.asarray(w, wdtype))
    return DeviceGraph(
        n=g.n,
        src=jsrc,
        dst=jdst,
        inv_deg=jnp.asarray(inv_deg, dtype),
        w=jw,
    )


class SlotPatch:
    """The slots an edge-update batch rewrites, with their new values.

    Produced host-side by `EdgeSlots.apply_delta`, consumed by
    `patch_device_graph`. `slots`/`src`/`dst`/`w` cover every edge-array slot
    that changes (freed slots zeroed back to padding, allocated slots
    carrying the new edges, and every surviving slot whose source vertex
    changed degree — its folded 1/deg weight moved); `rows`/`inv_deg` are the
    touched rows of the per-vertex inverse-degree vector.
    """

    __slots__ = ("slots", "src", "dst", "w", "rows", "inv_deg", "mirror")

    def __init__(self, slots, src, dst, w, rows, inv_deg, mirror=None):
        self.slots = slots        # [s] int64
        self.src = src            # [s] int32
        self.dst = dst            # [s] int32
        self.w = w                # [s] float64 (cast to device dtype at set)
        self.rows = rows          # [t] int64
        self.inv_deg = inv_deg    # [t] float64
        self.mirror = mirror      # the EdgeSlots this patch came from


def _sorted_delete(arr: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """arr without the rows at (sorted, unique) positions `pos` — a chain of
    contiguous block copies instead of np.delete's generic masking (hot:
    every update batch rewrites the sorted edge-key table, and the batch is
    tiny next to the table)."""
    if pos.size == 0:
        return arr
    pieces = [arr[s:e] for s, e in
              zip(np.concatenate([[0], pos + 1]),
                  np.concatenate([pos, [arr.shape[0]]]))]
    return np.concatenate(pieces)


def _sorted_insert(arr: np.ndarray, pos: np.ndarray,
                   vals: np.ndarray) -> np.ndarray:
    """arr with vals[i] inserted before original position pos[i] (pos sorted
    ascending, ties keep vals order — np.insert semantics), as a chain of
    contiguous block copies."""
    if pos.size == 0:
        return arr
    bounds = np.concatenate([[0], pos, [arr.shape[0]]])
    pieces = []
    for i in range(pos.size):
        pieces.append(arr[bounds[i]:bounds[i + 1]])
        pieces.append(vals[i:i + 1])
    pieces.append(arr[bounds[pos.size]:])
    return np.concatenate(pieces)


class EdgeSlots:
    """Host-side mirror of a padded DeviceGraph's edge slots.

    The serving registry keeps one per registered graph so an edge-update
    batch can be applied as a *patch* — rewrite only the affected slots of
    the padded device arrays — instead of the full O(m log m) host rebuild +
    device re-upload. Invariants mirrored from `Graph.from_undirected_edges`
    + `device_graph`:

      * every undirected edge occupies exactly two directed slots (lo->hi
        and hi->lo); self loops (the isolated-vertex patch that keeps P
        column-stochastic) occupy one;
      * padding slots are (src=0, dst=0, w=0) — zero weight keeps them out
        of every segment_sum and out of the CSR;
      * w[slot] = 1/max(deg, 1) of the slot's source vertex, computed in
        float64 and cast at device transfer, so a patched array is
        bit-identical to a freshly built one.

    The undirected edge table (`ekeys` sorted, `eslots` aligned) is kept
    sorted *incrementally* (searchsorted + block-memcpy insert/delete), so
    a batch costs O(batch log m + cap) — no sort over the edge set. The
    free list stays sorted the same way, allocation takes its TAIL (highest
    slots first — O(1) slicing) and freed slots are zeroed and merged back
    in place, which keeps the whole state machine deterministic:
    insert-then-delete of the same batch restores every array bit-for-bit.
    """

    def __init__(self, n: int, cap: int, src, dst, w64, live, deg, iso_slot,
                 ekeys, eslots, free):
        self.n = n
        self.cap = cap
        self.src = src            # [cap] int32
        self.dst = dst            # [cap] int32
        self.w64 = w64            # [cap] float64 exact weights (0 = padding)
        self.live = live          # [cap] bool
        self.deg = deg            # [n] int64 undirected degree, loops excluded
        self.iso_slot = iso_slot  # [n] int64 self-loop slot, -1 if none
        self.ekeys = ekeys        # [m_u] int64 sorted canonical keys
        self.eslots = eslots      # [m_u, 2] int64 (lo->hi, hi->lo) slots
        self.free = free          # sorted int64 array of dead slots

    @classmethod
    def from_graph(cls, g: Graph, cap: int | None = None) -> "EdgeSlots":
        """Build the mirror for a graph laid out like `device_graph(g,
        pad_edges_to=cap)`. Raises ValueError if the graph does not follow
        the `from_undirected_edges` contract (paired directions, self loops
        only on otherwise-isolated vertices) — callers then fall back to
        full rebuilds for that graph."""
        n, m = g.n, g.m
        cap = m if cap is None else cap
        if cap < m:
            raise ValueError(f"cap {cap} < edge count {m}")
        check_int32_range(n, cap, what="EdgeSlots")
        src = np.zeros(cap, np.int32)
        dst = np.zeros(cap, np.int32)
        src[:m] = g.src
        dst[:m] = g.dst
        live = np.zeros(cap, bool)
        live[:m] = True
        loop = src[:m] == dst[:m]
        deg = np.bincount(g.src[~loop], minlength=n).astype(np.int64)
        loop_v = src[:m][loop]
        if np.unique(loop_v).size != loop_v.size or np.any(deg[loop_v] > 0):
            raise ValueError("self loops must be unique and only on "
                             "otherwise-isolated vertices")
        iso_slot = np.full(n, -1, np.int64)
        iso_slot[loop_v] = np.flatnonzero(loop)
        fwd = np.flatnonzero(src[:m] < dst[:m])
        rev = np.flatnonzero(src[:m] > dst[:m])
        kf = src[fwd].astype(np.int64) * n + dst[fwd]
        kr = dst[rev].astype(np.int64) * n + src[rev]
        of, orr = np.argsort(kf), np.argsort(kr)
        kf, kr = kf[of], kr[orr]
        if kf.size != kr.size or not np.array_equal(kf, kr) or \
                np.any(kf[1:] == kf[:-1]):
            raise ValueError("edges must be symmetrized and deduplicated")
        inv = 1.0 / np.maximum(deg, 1)
        # jaxlint: disable=JL003 -- EdgeSlots exact-weight contract, cast at transfer
        w64 = np.zeros(cap, np.float64)
        w64[:m] = inv[src[:m]]
        return cls(n=n, cap=cap, src=src, dst=dst, w64=w64, live=live,
                   deg=deg, iso_slot=iso_slot, ekeys=kf,
                   eslots=np.stack([fwd[of], rev[orr]], axis=1),
                   free=np.arange(m, cap, dtype=np.int64))

    def to_device(self, dtype=jnp.float32, weight_dtype=None,
                  chunk_edges: int | None = None) -> DeviceGraph:
        """DeviceGraph over the mirror — identical arrays to
        `device_graph(g, pad_edges_to=cap)` on the same graph (same
        weight_dtype/chunk_edges semantics too).

        src/dst are handed over as private COPIES: jax's CPU backend
        zero-copies aligned numpy arrays, and the mirror mutates its
        buffers in place on every apply_delta — an aliased device array
        would silently drift. (The float64 weights convert, which already
        makes a fresh buffer.)"""
        wdtype = jnp.dtype(dtype) if weight_dtype is None else \
            jnp.dtype(weight_dtype)
        inv = 1.0 / np.maximum(self.deg, 1)
        if chunk_edges is not None and chunk_edges > 0:
            jsrc = _chunked_device_1d(self.src.copy(), jnp.int32, chunk_edges)
            jdst = _chunked_device_1d(self.dst.copy(), jnp.int32, chunk_edges)
            jw = _chunked_device_1d(self.w64, wdtype, chunk_edges)
        else:
            jsrc, jdst, jw = (jnp.asarray(self.src.copy()),
                              jnp.asarray(self.dst.copy()),
                              jnp.asarray(self.w64, wdtype))
        return DeviceGraph(n=self.n, src=jsrc, dst=jdst,
                           inv_deg=jnp.asarray(inv, dtype), w=jw)

    def to_graph(self) -> Graph:
        """Host Graph of the live slots (slot order, which is NOT the
        dst-sorted order of a fresh `from_undirected_edges` build — fine for
        every consumer: segment ops are order-free and CSR views sort)."""
        idx = np.flatnonzero(self.live)
        return Graph(n=self.n, src=self.src[idx], dst=self.dst[idx])

    def apply_delta(self, delta) -> SlotPatch | None:
        """Mutate the mirror by an EdgeDelta; return the device patch.

        Returns None — with the mirror UNTOUCHED — when the batch does not
        fit the current slot capacity (the caller takes the full-rebuild
        fallback, which picks a bigger bucket).
        """
        n = self.n
        ins, dele, touched = delta.inserted, delta.deleted, delta.touched
        # pure degree bookkeeping first: abort cleanly if it doesn't fit
        deg_new = self.deg.copy()
        if dele.size:
            ends = np.concatenate([dele // n, dele % n])
            deg_new -= np.bincount(ends, minlength=n).astype(np.int64)
        if ins.size:
            ends = np.concatenate([ins // n, ins % n])
            deg_new += np.bincount(ends, minlength=n).astype(np.int64)
        loops_drop = touched[(self.deg[touched] == 0) & (deg_new[touched] > 0)
                             & (self.iso_slot[touched] >= 0)]
        loops_add = touched[(deg_new[touched] == 0)
                            & (self.iso_slot[touched] < 0)]
        need = 2 * ins.size + loops_add.size
        freed_count = 2 * dele.size + loops_drop.size
        if need > freed_count + self.free.size:
            return None

        # free the deleted edges' slots + obsolete self loops, zeroed back
        # to padding (also what makes insert-then-delete restore the arrays
        # bit-for-bit)
        pos = np.searchsorted(self.ekeys, dele)
        freed = np.concatenate([self.eslots[pos].ravel(),
                                self.iso_slot[loops_drop]])
        self.ekeys = _sorted_delete(self.ekeys, pos)
        self.eslots = _sorted_delete(self.eslots, pos)
        self.iso_slot[loops_drop] = -1
        self.src[freed] = 0
        self.dst[freed] = 0
        self.w64[freed] = 0.0
        self.live[freed] = False

        # merge the (small, sorted) freed batch into the sorted free list —
        # block memcpy, never a sort over the O(cap - m) list — and allocate
        # from the tail: deterministic placement at O(1) slicing cost
        freed_sorted = np.sort(freed)
        free_all = _sorted_insert(self.free,
                                  np.searchsorted(self.free, freed_sorted),
                                  freed_sorted)
        alloc = free_all[free_all.size - need:] if need else \
            free_all[:0]
        self.free = free_all[:free_all.size - need]
        lo, hi = ins // n, ins % n
        ea, eb = alloc[: ins.size], alloc[ins.size: 2 * ins.size]
        self.src[ea] = lo
        self.dst[ea] = hi
        self.src[eb] = hi
        self.dst[eb] = lo
        ls = alloc[2 * ins.size:]
        self.src[ls] = loops_add
        self.dst[ls] = loops_add
        self.live[alloc] = True
        self.iso_slot[loops_add] = ls
        posi = np.searchsorted(self.ekeys, ins)
        self.ekeys = _sorted_insert(self.ekeys, posi, ins)
        self.eslots = _sorted_insert(self.eslots, posi,
                                     np.stack([ea, eb], axis=1))

        # a touched vertex's degree moved -> the folded 1/deg weight of
        # EVERY live slot it sources changes, inserted slots included
        self.deg = deg_new
        inv = 1.0 / np.maximum(deg_new, 1)
        tmask = np.zeros(n, bool)
        tmask[touched] = True
        sweep = np.flatnonzero(tmask[self.src] & self.live)
        self.w64[sweep] = inv[self.src[sweep]]

        # no sort/dedup needed: a slot freed then re-allocated in the same
        # batch can appear in both halves, but both positions carry the
        # slot's FINAL values (gathered below), so the duplicate scatter
        # writes are idempotent
        slots = np.concatenate([freed, sweep])
        return SlotPatch(slots=slots, src=self.src[slots],
                         dst=self.dst[slots], w=self.w64[slots],
                         rows=touched, inv_deg=inv[touched], mirror=self)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_patch(src, dst, w, inv_deg, slots, s_new, d_new, w_new, rows,
                   inv_new):
    """One fused scatter for all four patched arrays (single compile per
    padded patch shape instead of four eager scatter compilations). The
    graph arrays are DONATED: XLA scatters into the existing buffers
    instead of copying the O(cap) arrays a batch only touches a sliver of.
    Callers must replace their references with the returned arrays
    (patch_device_graph does)."""
    return (src.at[slots].set(s_new), dst.at[slots].set(d_new),
            w.at[slots].set(w_new), inv_deg.at[rows].set(inv_new))


def _pad_pow2(idx: np.ndarray, vals: list, minimum: int = 256):
    """Pad scatter indices + values to a power-of-two length by repeating
    the last element (idempotent: same value written twice). Bounds the set
    of compiled scatter shapes across arbitrary update batches."""
    size = minimum
    while size < idx.size:
        size *= 2
    pad = size - idx.size
    if pad:
        idx = np.concatenate([idx, np.repeat(idx[-1:], pad)])
        vals = [np.concatenate([v, np.repeat(v[-1:], pad)]) for v in vals]
    return idx, vals


def patch_device_graph(dg: DeviceGraph, patch: SlotPatch) -> DeviceGraph:
    """Apply a SlotPatch to a padded DeviceGraph in place.

    Rewrites only the affected slots of src/dst/w and the touched rows of
    inv_deg via one fused scatter — array shapes are unchanged, so jitted
    solves over the graph (or an engine holding it) do not retrace, and the
    scatter's own index arrays are padded to power-of-two lengths so churny
    update streams reuse a handful of compiled shapes. The mutated dg is
    the SAME object (engines holding it see the update); the cached CSR
    view is dropped. Weight values are float64-exact and cast at set, so a
    patched array is bit-identical to a rebuilt one.
    """
    if dg.w is None:
        raise ValueError("patch_device_graph needs a DeviceGraph with "
                         "folded weights (device_graph builds one)")
    if patch.slots.size == 0 and patch.rows.size == 0:
        return dg
    m = patch.mirror
    if m is not None and patch.slots.size * 64 >= m.cap:
        # the patch is no longer a sliver: XLA's scatter costs ~100ns per
        # index while a host->device re-upload of the (already patched)
        # mirror streams at memcpy speed, so past ~cap/64 touched slots the
        # bulk transfer wins. Same float64-exact values either way.
        # src/dst go over as COPIES — jax's CPU backend zero-copies aligned
        # numpy buffers and the mirror mutates its arrays in place on the
        # next batch (the astype conversions below are already fresh).
        dg.src = jnp.asarray(m.src.copy())
        dg.dst = jnp.asarray(m.dst.copy())
        dg.w = jnp.asarray(m.w64.astype(np.dtype(dg.w.dtype)))
        dg.inv_deg = jnp.asarray(
            (1.0 / np.maximum(m.deg, 1)).astype(np.dtype(dg.inv_deg.dtype)))
        dg._csr = None
        return dg
    # an effective delta always touches >= 1 slot AND >= 1 vertex row, so
    # both scatters have something real to repeat into their padding
    slots, (s_new, d_new, w_new) = _pad_pow2(
        patch.slots, [patch.src, patch.dst, patch.w])
    rows, (inv_new,) = _pad_pow2(patch.rows, [patch.inv_deg], minimum=64)
    # dtype casts in numpy, arrays handed to jit raw: the jitted call does
    # one device_put per arg either way, and this skips the eager asarray
    # dispatch overhead per array
    dg.src, dg.dst, dg.w, dg.inv_deg = _scatter_patch(
        dg.src, dg.dst, dg.w, dg.inv_deg,
        slots, s_new.astype(np.dtype(dg.src.dtype), copy=False),
        d_new.astype(np.dtype(dg.dst.dtype), copy=False),
        w_new.astype(np.dtype(dg.w.dtype)),
        rows, inv_new.astype(np.dtype(dg.inv_deg.dtype)))
    dg._csr = None
    return dg


def _transition_matmul(dg: DeviceGraph, x: jax.Array) -> jax.Array:
    """Shared spmv/spmm body: y[dst] += w[e] * x[src] over the edge list.
    Weights may be stored packed (bf16); they upcast to the solve dtype at
    multiply time so the segment_sum accumulates at full precision."""
    with jax.named_scope("spmm.gather"):
        w = dg.w if dg.w is not None else dg.inv_deg[dg.src]
        if w.dtype != x.dtype:
            w = w.astype(x.dtype)
        contrib = x[dg.src] * (w if x.ndim == 1 else w[:, None])
    with jax.named_scope("spmm.scatter"):
        return jax.ops.segment_sum(contrib, dg.dst, num_segments=dg.n)


def spmv(dg: DeviceGraph, x: jax.Array) -> jax.Array:
    """y = P x with P = A D^{-1}: y[dst] += x[src] / deg[src]. x: [n]."""
    return _transition_matmul(dg, x)


def spmm(dg: DeviceGraph, x: jax.Array) -> jax.Array:
    """Batched transition: x [n, B] -> P x [n, B] (multi-source PageRank)."""
    return _transition_matmul(dg, x)


def aggregate(dg: DeviceGraph, x: jax.Array, kind: str = "sum",
              edge_vals: jax.Array | None = None) -> jax.Array:
    """Generic neighbour aggregation for GNN layers. x: [n, d]."""
    msgs = x[dg.src]
    if edge_vals is not None:
        msgs = msgs * edge_vals[:, None]
    if kind == "sum":
        return jax.ops.segment_sum(msgs, dg.dst, num_segments=dg.n)
    if kind == "mean":
        s = jax.ops.segment_sum(msgs, dg.dst, num_segments=dg.n)
        cnt = jax.ops.segment_sum(jnp.ones_like(dg.dst, msgs.dtype), dg.dst,
                                  num_segments=dg.n)
        return s / jnp.maximum(cnt, 1.0)[:, None]
    if kind == "max":
        return jax.ops.segment_max(msgs, dg.dst, num_segments=dg.n)
    if kind == "min":
        return jax.ops.segment_min(msgs, dg.dst, num_segments=dg.n)
    raise ValueError(kind)


def edge_softmax(dg: DeviceGraph, scores: jax.Array) -> jax.Array:
    """Softmax over incoming edges per destination vertex. scores: [m]."""
    mx = jax.ops.segment_max(scores, dg.dst, num_segments=dg.n)
    ex = jnp.exp(scores - mx[dg.dst])
    z = jax.ops.segment_sum(ex, dg.dst, num_segments=dg.n)
    return ex / z[dg.dst]


def degree_normalize(dg: DeviceGraph, x: jax.Array, power: float = -0.5) -> jax.Array:
    """D^power x (GCN-style normalization helper); deg = 1 / inv_deg.
    x: [n] or [n, d], like spmv/spmm."""
    scale = dg.inv_deg ** (-power)
    return x * (scale if x.ndim == 1 else scale[:, None])
