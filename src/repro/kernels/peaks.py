"""Pallas TPU kernel: the maximum of each lane of a stack of grids, and
where it lies.

A bank query ends in V correlation grids of (2B)^3 samples each; only the
largest real sample of each grid, its flat index and a few neighbours go
back to the host.  This kernel reads every grid once from HBM (it is
memory-bound: (2B)^3 x 4 bytes a lane in f32, 8.4 MB at B = 64) and keeps, per
lane, a running (1, 128) row of column maxima and the first row that holds
each.  The last block of a lane folds the 128 columns into one maximum and
the smallest flat index that reaches it, so ties go to the first index in
row-major order, as ``np.argmax`` has it.

Grid: (V, rows // tr); each step reads a (1, tr, 128) block of the lane's
grid laid out as (rows, 128).  The lane axis is parallel, the row axis a
reduction.  Outputs are (V, 1, 128) with the lane's result in every
column (a block must span the last two dims or tile them by (8, 128)).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import I0, resolve_interpret

__all__ = ["grid_peaks"]

LANES = 128
ROWS_PER_BLOCK = 2048           # 1 MB of f32 per block, 2 MB double-buffered
_NO_INDEX = np.int32(np.iinfo(np.int32).max)


def _peaks_kernel(x_ref, max_ref, idx_ref, best_ref, row_ref):
    r = pl.program_id(1)
    x = x_ref[0]                                          # (tr, 128)
    tr = x.shape[0]

    @pl.when(r == 0)
    def _init():
        best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, best_ref.dtype)
        row_ref[...] = jnp.zeros(row_ref.shape, jnp.int32)

    bmax = jnp.max(x, axis=0, keepdims=True)              # (1, 128)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) + r * tr
    brow = jnp.min(jnp.where(x == bmax, rows, _NO_INDEX), axis=0,
                   keepdims=True)
    # strictly greater: an equal maximum in a later block keeps the
    # earlier (smaller) row
    better = bmax > best_ref[...]
    best_ref[...] = jnp.where(better, bmax, best_ref[...])
    row_ref[...] = jnp.where(better, brow, row_ref[...])

    @pl.when(r == pl.num_programs(1) - 1)
    def _finish():
        best = best_ref[...]
        top = jnp.max(best, axis=1, keepdims=True)        # (1, 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, best.shape, 1)
        flat = row_ref[...] * LANES + cols
        first = jnp.min(jnp.where(best == top, flat, _NO_INDEX), axis=1,
                        keepdims=True)
        max_ref[0] = jnp.broadcast_to(top, best.shape)
        idx_ref[0] = jnp.broadcast_to(first, best.shape)


@partial(jax.jit, static_argnames=("interpret",))
def grid_peaks(x, *, interpret=None):
    """Per-lane maximum and flat argmax of a real stack.

    x: (V, ...) real; each lane is flattened in row-major order.  Returns
    (max (V,), index (V,) int32), the index the first at which the lane
    reaches its maximum, as ``np.argmax`` of the flattened lane.
    """
    interpret = resolve_interpret(interpret)
    V = x.shape[0]
    x = x.reshape(V, -1)
    n = x.shape[1]
    rows = -(-n // LANES)
    tr = min(ROWS_PER_BLOCK, -(-rows // 8) * 8)
    rows = -(-rows // tr) * tr
    if rows * LANES != n:          # padding never wins: -inf past the end
        x = jnp.pad(x, ((0, 0), (0, rows * LANES - n)),
                    constant_values=-jnp.inf)
    x = x.reshape(V, rows, LANES)
    out = jax.ShapeDtypeStruct((V, 1, LANES), x.dtype)
    idx = jax.ShapeDtypeStruct((V, 1, LANES), jnp.int32)
    top, first = pl.pallas_call(
        _peaks_kernel,
        grid=(V, rows // tr),
        in_specs=[pl.BlockSpec((1, tr, LANES), lambda v, r: (v, r, I0))],
        out_specs=[pl.BlockSpec((1, 1, LANES), lambda v, r: (v, I0, I0)),
                   pl.BlockSpec((1, 1, LANES), lambda v, r: (v, I0, I0))],
        out_shape=[out, idx],
        scratch_shapes=[pltpu.VMEM((1, LANES), x.dtype),
                        pltpu.VMEM((1, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="grid_peaks",
    )(x)
    return top[:, 0, 0], first[:, 0, 0]
