"""The main-path kernels and the serve solve, compiled for a TPU v5e that is
described, not attached. No chip is needed: the TPU compiler ships with
libtpu and refuses here what it would refuse on the chip — SMEM or VMEM
overflows, unaligned tiles, programs that outgrow the 16 GB of HBM.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and every pytest-xdist worker
imports this file. Nothing here runs; these compiles say nothing about
results or times.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import re

from repro.core.chebyshev import default_chunk, make_schedule
from repro.core.engine import (CooEngine, EllEngine, FusedBlockEllEngine,
                               HubTailEngine)
from repro.core.pagerank import cpaa_fixed
from repro.graph.ops import DeviceGraph
from repro.kernels.bsr_spmm.bsr_spmm import MAX_PREFETCH_SLOTS, bsr_spmm_pallas
from repro.kernels.cheb_step.cheb_step import cheb_step_pallas
from repro.serve.pagerank_service import _solve_topk_adaptive

# mesh-1m = paper_dataset("NACA0015", 10.0): n = 1,040,000 = 8125 row
# blocks of 128, at most 8 tiles per row block after the BFS reorder; the
# benchmark's 1040 x 1000 triangulated grid has 6,231,842 directed edges
MESH_N, MESH_RB, MESH_S = 1_040_000, 8125, 8
MESH_M = 6_231_842
# its neighbour table: maximum degree 6, so [6, n] int32 slots
MESH_K = 6
# chunglu-1m's hub/tail split at the default hub bar (deg >= 32, panels
# of 32 columns): hubs, hub panels, self-loop rows and the tail's degree
# slices as (table width, rows)
CL_N, CL_HUBS, CL_PANELS, CL_COPY = 1_000_000, 44_148, 299_850, 86_110
CL_SLICES = ((31, 4_757), (28, 5_893), (25, 7_540), (22, 6_467), (20, 7_568),
             (18, 9_518), (16, 12_434), (14, 16_332), (12, 23_003),
             (10, 15_208), (9, 19_374), (8, 24_876), (7, 32_864), (6, 45_487),
             (5, 65_548), (4, 96_570), (3, 137_617), (2, 173_808),
             (1, 164_878))
# graph500-19's split (the Graph 500 Kronecker draw at scale 19, seed 0)
G500_N, G500_HUBS, G500_PANELS, G500_COPY = 524_288, 57_943, 450_926, \
    188_967
G500_SLICES = ((31, 10_524), (28, 10_955), (25, 8_401), (22, 4_482),
               (19, 1_583), (17, 1_747), (15, 4_073), (13, 9_418),
               (11, 7_344), (10, 8_934), (9, 10_186), (8, 10_544),
               (7, 10_457), (6, 10_963), (5, 13_199), (4, 18_233),
               (3, 26_503), (2, 39_949), (1, 69_883))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip. The persistent
    compilation cache is off meanwhile: a described-chip compile can be
    written to it but never read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("bt", [1, 128])
def test_bsr_spmm_at_mesh_1m(spec, bt):
    compiled = bsr_spmm_pallas.lower(
        spec((MESH_RB, MESH_S), jnp.int32),
        spec((MESH_RB, MESH_S, 128, 128), jnp.float32),
        spec((MESH_N, bt), jnp.float32)).compile()
    assert _kernel_calls(compiled) == 1


def test_bsr_spmm_above_one_smem_prefetch(spec):
    # 520,000 slots: twice the ~2.6e5 a single flat prefetch fits in SMEM,
    # so the row blocks split over several calls. 32x32 tiles keep the
    # values tensor inside HBM (128x128 f32 tiles at this slot count
    # would need 34 GB).
    n_rb, s, blk = 32_500, 16, 32
    compiled = bsr_spmm_pallas.lower(
        spec((n_rb, s), jnp.int32), spec((n_rb, s, blk, blk), jnp.float32),
        spec((n_rb * blk, 128), jnp.float32)).compile()
    rows_per_call = MAX_PREFETCH_SLOTS // s
    assert _kernel_calls(compiled) == -(-n_rb // rows_per_call) > 1


def test_cheb_step_at_1m_by_128(spec):
    rows = 10**6 * 128 // 128      # n = 10^6, B = 128, as [rows, 128] lanes
    vec = spec((rows, 128), jnp.float32)
    compiled = cheb_step_pallas.lower(vec, vec, vec,
                                      spec((1,), jnp.float32)).compile()
    assert _kernel_calls(compiled) == 1


def _fused_engine(spec):
    # use_kernel=True: the engine would ask jax.default_backend(), which
    # says cpu here
    children = (spec((MESH_RB, MESH_S), jnp.int32),
                spec((MESH_RB, MESH_S, 128, 128), jnp.float32),
                spec((MESH_N,), jnp.int32), spec((MESH_N,), jnp.int32))
    return FusedBlockEllEngine.tree_unflatten((MESH_N, 128, True, False),
                                              children)


def _hub_tail_engine(spec, n=CL_N, hubs=CL_HUBS, panels=CL_PANELS,
                     copy=CL_COPY, slices=CL_SLICES):
    i32 = jnp.int32
    children = (spec((n,), jnp.float32), spec((n,), i32), spec((n,), i32),
                spec((panels, 32), i32), spec((panels,), i32),
                tuple(spec(shape, i32) for shape in slices))
    return HubTailEngine.tree_unflatten((n, hubs, copy, 32, 32, "float32"),
                                        children)


@pytest.mark.parametrize("build, kernels", [(_fused_engine, True),
                                            (_hub_tail_engine, False)],
                         ids=["fused", "hub_tail"])
def test_serve_solve_compiles(spec, build, kernels):
    """The service's adaptive tick at the pagerank-serve operating point
    (c=0.85, tol=1e-4, a 128-query bucket, top-32)."""
    eng = build(spec)
    # the pytree round trip keeps the described shapes and the aux data
    leaves, treedef = jax.tree_util.tree_flatten(eng)
    assert jax.tree_util.tree_unflatten(treedef, leaves).n == eng.n
    c, tol = 0.85, 1e-4
    compiled = _solve_topk_adaptive.lower(
        eng, spec((eng.n, 128), jnp.float32), c, tol,
        max_rounds=make_schedule(c, tol).rounds,
        chunk=default_chunk(c, tol), k=32).compile()
    assert (_kernel_calls(compiled) > 0) == kernels


def _op_names(text) -> dict[str, list[str]]:
    """Op kind (gather, scatter-add, fusion) -> the op_name of each."""
    ops = {}
    op_name = re.compile(
        r'= \S+ (gather|scatter-add|fusion)\(.*op_name="([^"]*)"')
    for line in text.splitlines():
        m = op_name.search(line)
        if m:
            ops.setdefault(m.group(1), []).append(m.group(2))
    return ops


def test_spmm_scopes_reach_the_compiled_program(spec):
    """The global solve at mesh-1m's size on COO: every scatter-add and
    every gather the chip's compiler emits keeps its named scope in the
    op metadata, which the profiler's op events carry."""
    i32, f32 = jnp.int32, jnp.float32
    dg = DeviceGraph.tree_unflatten(MESH_N, (
        spec((MESH_M,), i32), spec((MESH_M,), i32), spec((MESH_N,), f32),
        spec((MESH_M,), f32)))
    sched = make_schedule(0.85, 1e-6)
    text = cpaa_fixed.lower(
        CooEngine(dg), spec((sched.rounds + 1,), f32), spec((MESH_N,), f32),
        rounds=sched.rounds).compile().as_text()
    fusions = _op_names(text).get("fusion", [])
    assert any(p.endswith("spmm.scatter/scatter-add") for p in fusions)
    assert any(p.endswith("spmm.gather/gather") for p in fusions)
    for p in fusions:
        if p.endswith("/scatter-add"):
            assert "/spmm.scatter/" in p, p
        if p.endswith("/gather"):
            assert "/spmm.gather/" in p, p


def _ell_engine(spec):
    children = (spec((MESH_N,), jnp.float32),
                spec((MESH_K, MESH_N), jnp.int32))
    return EllEngine.tree_unflatten((MESH_N, "float32"), children)


def _assert_gather_without_scatter(text):
    assert re.search(r'op_name="[^"]*/spmm\.gather/', text)
    assert not re.search(r"= \S+ scatter\(", text)


def test_ell_global_solve_holds_no_scatter(spec):
    """The global solve at mesh-1m's size on the neighbour table, B = 1:
    the gather reaches the program under its scope and no scatter does."""
    sched = make_schedule(0.85, 1e-6)
    f32 = jnp.float32
    text = cpaa_fixed.lower(
        _ell_engine(spec), spec((sched.rounds + 1,), f32),
        spec((MESH_N,), f32), rounds=sched.rounds).compile().as_text()
    _assert_gather_without_scatter(text)


def test_ell_serve_solve_holds_no_scatter(spec):
    """The service's adaptive 128-column tick on the same table."""
    c, tol = 0.85, 1e-4
    text = _solve_topk_adaptive.lower(
        _ell_engine(spec), spec((MESH_N, 128), jnp.float32), c, tol,
        max_rounds=make_schedule(c, tol).rounds,
        chunk=default_chunk(c, tol), k=32).compile().as_text()
    _assert_gather_without_scatter(text)


def test_hub_tail_halves_reach_the_compiled_program(spec):
    """The global solve at graph500-19's split: every gather and
    scatter-add the compiler emits sits under the half it belongs to,
    hub_tail.panel or hub_tail.tail, with spmm.gather / spmm.scatter still
    the innermost scope (what bench/program_trace.py reads), or is one of
    the internal order's permutation gathers under hub_tail.order. The
    tail gathers its degree slices and scatters nothing."""
    f32 = jnp.float32
    sched = make_schedule(0.85, 1e-6)
    eng = _hub_tail_engine(spec, G500_N, G500_HUBS, G500_PANELS, G500_COPY,
                           G500_SLICES)
    text = cpaa_fixed.lower(
        eng, spec((sched.rounds + 1,), f32), spec((G500_N,), f32),
        rounds=sched.rounds).compile().as_text()
    paths = [p for names in _op_names(text).values() for p in names
             if p.endswith(("/gather", "/scatter-add"))]
    for p in paths:
        if p.endswith("/hub_tail.order/gather"):
            continue
        assert "/hub_tail.panel/" in p or "/hub_tail.tail/" in p, p
        kernel = "spmm.gather" if p.endswith("/gather") else "spmm.scatter"
        assert p.rsplit("/", 2)[-2] == kernel, p
    assert not any("/hub_tail.tail/" in p and p.endswith("/scatter-add")
                   for p in paths)
    assert any(p.endswith("/hub_tail.tail/spmm.gather/gather")
               for p in paths)
    assert any(p.endswith("/hub_tail.panel/spmm.gather/gather")
               for p in paths)
