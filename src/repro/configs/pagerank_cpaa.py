"""cpaa-pagerank — the paper's own workload as a production config.

These cells are EXTRA beyond the 40 assigned (arch x shape) cells: they dry-
run the distributed CPAA solver itself at cluster scale, and are the
"most representative of the paper's technique" hillclimb target (§Perf).

Shapes (synthetic, matched to the paper's dataset families at pod scale):
  pr_mesh_67m      n=2^26, deg 6 (NACA/M6/NLR-like mesh), 1D partition
  pr_kmer_550m     n=5.5e8, deg 2.13 (kmer-V2 x10), 1D partition
  pr_mesh_67m_b128 n=2^26, deg 6, 128 personalization columns (the TPU
                   batched-SpMM adaptation; feeds the MXU)
  pr_mesh_67m_2d   n=2^26, deg 6, 2D grid partition (beyond-paper comm
                   optimization: all-gather O(n) -> O(n/R + n/C))

Rounds: 12 (= ERR < 1e-3 at c=0.85, the paper's Table 2 operating point).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import Cell, DryRunPlan
from repro.core.chebyshev import make_schedule
from repro.core import distributed as dist

NAME = "cpaa-pagerank"
FAMILY = "pagerank"

C = 0.85
TOL = 1e-3
LANE = 128
IMBALANCE = 1.15   # per-device edge-count padding factor
# solve-engine format ("auto" | "coo" | "hub-tail" | "ell" | "block_ell" |
# "fused" | "sharded-1d" | "sharded-2d"); the distributed dry-run cells build
# their partition from the SHAPES table regardless, but smoke_run and local
# solves route through core/engine.select_engine — "auto" shards when the
# process has >= 2 devices and the graph clears the collective-amortization
# bar.
ENGINE = "auto"
# sharded-engine mesh knobs for smoke_run/local solves: (R, C) grid for
# sharded-2d (None = most-square factorization of the device count) and the
# partition padding lane.
MESH_GRID = None
PARTITION_LANE = 128
# residual-controlled solves: exit when the measured chunked L1 residual
# reaches TOL instead of always running the a-priori round count (which
# stays the hard cap); None chunk = core.chebyshev.default_chunk(C, TOL).
ADAPTIVE = True
ADAPTIVE_CHUNK = None

SHAPES = {
    "pr_mesh_67m": dict(kind="pagerank", n=1 << 26, deg=6.0, batch=None,
                        partition="1d"),
    "pr_kmer_550m": dict(kind="pagerank", n=550_000_000, deg=2.13,
                         batch=None, partition="1d"),
    "pr_mesh_67m_b128": dict(kind="pagerank", n=1 << 26, deg=6.0, batch=128,
                             partition="1d"),
    "pr_mesh_67m_2d": dict(kind="pagerank", n=1 << 26, deg=6.0, batch=None,
                           partition="2d"),
    # beyond-paper: bf16 wire format for the row all-gather (halves the
    # dominant collective; reductions stay f32). Rank-stable for tol>=1e-2
    # targets — numerics measured in tests/distributed_check.py.
    "pr_mesh_67m_2d_bf16": dict(kind="pagerank", n=1 << 26, deg=6.0,
                                batch=None, partition="2d",
                                comm_dtype="bfloat16"),
    # beyond-paper: 2D partition x 128 personalization columns — the full
    # TPU adaptation (batched SpMM feeds the MXU; comm O(n/R + n/C) per col)
    "pr_mesh_67m_2d_b128": dict(kind="pagerank", n=1 << 26, deg=6.0,
                                batch=128, partition="2d"),
}


@dataclass(frozen=True)
class _AbstractPart1D:
    n: int
    n_orig: int
    n_dev: int
    rows_per_dev: int
    edges_per_dev: int


@dataclass(frozen=True)
class _AbstractPart2D:
    n: int
    n_orig: int
    grid: tuple[int, int]
    rows_per_chunk: int
    cols_per_chunk: int
    sub: int
    edges_per_dev: int


def _round_up(x, q):
    return ((x + q - 1) // q) * q


def abstract_partition_1d(n_orig: int, m: int, n_dev: int) -> _AbstractPart1D:
    n = _round_up(n_orig, n_dev * LANE)
    e_pad = _round_up(int(m / n_dev * IMBALANCE), LANE)
    return _AbstractPart1D(n=n, n_orig=n_orig, n_dev=n_dev,
                           rows_per_dev=n // n_dev, edges_per_dev=e_pad)


def abstract_partition_2d(n_orig: int, m: int, grid) -> _AbstractPart2D:
    r, c = grid
    n = _round_up(n_orig, r * c * LANE)
    e_pad = _round_up(int(m / (r * c) * IMBALANCE), LANE)
    return _AbstractPart2D(n=n, n_orig=n_orig, grid=grid,
                           rows_per_chunk=n // r, cols_per_chunk=n // c,
                           sub=n // (r * c), edges_per_dev=e_pad)


def full_config():
    return {"c": C, "tol": TOL, "rounds": make_schedule(C, TOL).rounds,
            "engine": ENGINE, "mesh_grid": MESH_GRID,
            "partition_lane": PARTITION_LANE,
            "adaptive": ADAPTIVE, "adaptive_chunk": ADAPTIVE_CHUNK}


def smoke_config():
    return full_config()


def cells():
    return [Cell(shape=s, kind="pagerank") for s in SHAPES]


def model_flops(n: int, m: int, rounds: int, batch: int | None) -> float:
    """Paper §4.2.3: m mults + (m + 2n) adds per iteration (per column)."""
    b = batch or 1
    return rounds * (2.0 * m + 2.0 * n) * b


def build(shape: str, multi_pod: bool, _rounds: int | None = None):
    info = SHAPES[shape]
    n, m = info["n"], int(info["n"] * info["deg"])
    sched = make_schedule(C, TOL) if _rounds is None \
        else make_schedule(C, rounds=_rounds)
    batched = info["batch"] is not None

    if info["partition"] == "1d":
        n_dev = 512 if multi_pod else 256
        part = abstract_partition_1d(n, m, n_dev)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")

        def step_builder(mesh):
            return dist.cpaa_distributed_1d(mesh, axes, part, sched,
                                            batched=batched,
                                            unroll=_rounds is not None)

        e = part.edges_per_dev
        vec_shape = (part.n, info["batch"]) if batched else (part.n,)
        args = (
            jax.ShapeDtypeStruct(vec_shape, jnp.float32),
            jax.ShapeDtypeStruct((n_dev, e), jnp.int32),
            jax.ShapeDtypeStruct((n_dev, e), jnp.int32),
            jax.ShapeDtypeStruct((n_dev, e), jnp.float32),
        )
        vec_spec = P(axes, None) if batched else P(axes)
        specs = (vec_spec, P(axes), P(axes), P(axes))
    else:
        grid = (32, 16) if multi_pod else (16, 16)
        part = abstract_partition_2d(n, m, grid)
        row_axis = ("pod", "data") if multi_pod else ("data",)

        cdt = info.get("comm_dtype")
        cdt = jnp.dtype(cdt) if cdt else None

        def step_builder(mesh):
            return dist.cpaa_distributed_2d(mesh, row_axis, "model", part,
                                            sched, batched=batched,
                                            unroll=_rounds is not None,
                                            comm_dtype=cdt)

        e = part.edges_per_dev
        vec_shape = (part.n, info["batch"]) if batched else (part.n,)
        args = (
            jax.ShapeDtypeStruct(vec_shape, jnp.float32),
            jax.ShapeDtypeStruct((*grid, e), jnp.int32),
            jax.ShapeDtypeStruct((*grid, e), jnp.int32),
            jax.ShapeDtypeStruct((*grid, e), jnp.float32),
        )
        es = P(row_axis, "model")
        vec_spec = P("model", None) if batched else P("model")
        specs = (vec_spec, es, es, es)

    def probe(L, M):
        p = build(shape, multi_pod, _rounds=L)
        return p

    plan = DryRunPlan(step=None, abstract_args=args, in_specs=specs,
                      static={"step_builder": step_builder},
                      model_flops=model_flops(n, m, sched.rounds,
                                              info["batch"]),
                      note=f"rounds={sched.rounds} partition={info['partition']}")
    if _rounds is None:
        plan.cost_model = {"L": sched.rounds, "M": 1, "probe": probe}
    return plan


def smoke_run(seed: int = 0):
    """CPU: CPAA (fixed + residual-controlled) on a small mesh graph vs
    direct solve; reports the adaptive solver's round savings."""
    import numpy as np
    from repro.core import (cpaa, cpaa_adaptive, select_engine,
                            true_pagerank_dense)
    from repro.graph import generators
    g = generators.tri_mesh(9, 11)
    eng = select_engine(g, mode=ENGINE, grid=MESH_GRID, lane=PARTITION_LANE)
    pi = np.asarray(cpaa(eng, C, 1e-8).pi, np.float64)
    pi_true = true_pagerank_dense(g, C)
    res_a = cpaa_adaptive(eng, C, 1e-8, chunk=ADAPTIVE_CHUNK)
    err_a = np.max(np.abs(np.asarray(res_a.pi, np.float64) - pi_true)
                   / pi_true)
    return {"max_rel_err": jnp.float32(np.max(np.abs(pi - pi_true) / pi_true)),
            "adaptive_max_rel_err": jnp.float32(err_a),
            "adaptive_rounds": jnp.float32(res_a.iterations),
            "adaptive_rounds_bound": jnp.float32(res_a.rounds_bound),
            "loss": jnp.float32(0.0)}
