"""Block-ELL SpMM Pallas kernel — y = P @ X with P in 128x128 block-sparse
ELL format (graph.structure.BlockEll) and X dense [n, B].

TPU adaptation of the paper's per-vertex pull loop (Algorithm 1 lines
11-15): instead of one scalar gather per edge, vertices are BFS-reordered so
edges cluster into BxB tiles, and each tile is a dense (B, B) x (B, BT)
matmul on the MXU. The ELL slot list per row block gives a static grid; the
column-block id of every slot is scalar-prefetched so the x tile for slot s
of row block i is DMA'd by BlockSpec index_map — no in-kernel gathers.

Grid: (row blocks of one call, S). Slot s is the fastest axis, so the
output tile for row block i stays resident in VMEM across its S
accumulation steps (consecutive-revisit rule).

Scalar prefetch lives in SMEM (1 MiB on v5e), which pads a 2-D array's
last dimension to 128 lanes: prefetching `block_cols [n_rb, S]` as is
costs n_rb * 128 words and overflows SMEM at ~2000 row blocks. The index
is therefore prefetched FLAT (`idx[i * S + s]`), and the row-block range
is split into calls of at most `max_prefetch` slots each. Every call reads
the full `values` array through an offset index_map (no slice copy of the
tiles) and writes its rows of one shared output buffer: the first call
creates it, each later call aliases it in place, so the split costs no
extra pass over y.

VMEM footprint per step: values tile B*B*4 + x tile B*BT*4 + y tile B*BT*4
= 64 KiB + 2 * BT KiB for B=128 — comfortably inside the ~16 MiB VMEM, with
room for double buffering of the values/x streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int32 slots scalar-prefetched per call: 256 KiB of the 1 MiB SMEM
MAX_PREFETCH_SLOTS = 1 << 16


def _kernel(idx_ref, vals_ref, x_ref, *refs):
    y_ref = refs[-1]    # refs[0] is the aliased output buffer on later calls
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    tile = vals_ref[0, 0]          # [B, B]
    xblk = x_ref[...]              # [B, BT]
    # HIGHEST: the MXU's default f32 matmul rounds its inputs to bf16, which
    # perturbs every 1/deg weight by up to 2^-9 and leaves P no longer
    # column-stochastic. Measured on a TPU v5e: relative error 2.6e-3
    # against float64 at the default precision, 8.9e-8 at HIGHEST. Interpret
    # mode computes in f32 and cannot show the difference.
    y_ref[...] += jnp.dot(tile, xblk, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


def _rows_call(idx, values, x, y, row0: int, rows: int, interpret: bool):
    """One pallas_call over row blocks [row0, row0 + rows). `idx` is the
    flat slot index of those rows; `y` (None on the first call) is the
    output buffer the call writes in place."""
    _, s_max, blk, _ = values.shape
    n, bt = x.shape
    in_specs = [
        pl.BlockSpec((1, 1, blk, blk),
                     lambda i, s, idx: (row0 + i, s, 0, 0)),
        pl.BlockSpec((blk, bt), lambda i, s, idx: (idx[i * s_max + s], 0)),
    ]
    args = [idx, values, x]
    aliases = {}
    if y is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(y)
        aliases = {3: 0}    # operand 3 (after the prefetch idx) -> output 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, s_max),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk, bt), lambda i, s, idx: (row0 + i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, bt), jnp.float32),
        input_output_aliases=aliases,
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("interpret", "max_prefetch"))
def bsr_spmm_pallas(block_cols: jax.Array, values: jax.Array, x: jax.Array,
                    *, interpret: bool = False,
                    max_prefetch: int = MAX_PREFETCH_SLOTS) -> jax.Array:
    """block_cols [n_rb, S] int32; values [n_rb, S, B, B] f32; x [n, BT] f32.

    Returns y [n, BT] with n = n_rb * B. `max_prefetch` bounds the slots
    whose column ids one call prefetches into SMEM.
    """
    n_rb, s_max, blk, blk2 = values.shape
    assert blk == blk2, values.shape
    n, bt = x.shape
    assert n == n_rb * blk, (n, n_rb, blk)
    assert block_cols.shape == (n_rb, s_max), (block_cols.shape, values.shape)

    flat = block_cols.reshape(-1)
    rows_per_call = max(1, max_prefetch // s_max)
    y = None
    for row0 in range(0, n_rb, rows_per_call):
        rows = min(rows_per_call, n_rb - row0)
        y = _rows_call(flat[row0 * s_max:(row0 + rows) * s_max], values, x,
                       y, row0, rows, interpret)
    return y
