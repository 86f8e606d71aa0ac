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

from repro.core.chebyshev import default_chunk, make_schedule
from repro.core.engine import FusedBlockEllEngine, HubTailEngine
from repro.kernels.bsr_spmm.bsr_spmm import MAX_PREFETCH_SLOTS, bsr_spmm_pallas
from repro.kernels.cheb_step.cheb_step import cheb_step_pallas
from repro.serve.pagerank_service import _solve_topk_adaptive

# mesh-1m = paper_dataset("NACA0015", 10.0): n = 1,040,000 = 8125 row
# blocks of 128, at most 8 tiles per row block after the BFS reorder
MESH_N, MESH_RB, MESH_S = 1_040_000, 8125, 8
# chunglu-1m's hub/tail split at the default hub bar (deg >= 32, panels
# of 32 columns): hubs, tail edges and hub panels
CL_N, CL_HUBS, CL_TAIL, CL_PANELS = 1_000_000, 44_148, 4_364_154, 299_850


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


def _hub_tail_engine(spec):
    i32 = jnp.int32
    children = (spec((CL_N,), jnp.float32), spec((CL_TAIL,), i32),
                spec((CL_TAIL,), i32), spec((CL_PANELS, 32), i32),
                spec((CL_PANELS,), i32), spec((CL_HUBS,), i32))
    return HubTailEngine.tree_unflatten((CL_N, 32, 32, "float32"), children)


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
