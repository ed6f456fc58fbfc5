"""Pallas TPU kernel: FUSED ragged + on-the-fly clustered DWT.

The two big levers of the paper's DWT stage lived in separate kernels:

  * dwt.py (ragged)        -- skip the l < max(|m|,|m'|) zero-triangle via a
    host-enumerated work list (paper point P3), but reads the precomputed
    Wigner-d table from HBM (~0.37 TB at B = 512 in f64);
  * wigner_rec.py          -- generate the d-rows on the fly from the
    three-term recurrence (paper Eq. 2) so the table never touches HBM,
    but marches l from 0 and therefore still *executes* the zero-triangle.

This kernel family gets both at once, plus multi-transform lane batching:

  * clusters are host-sorted by ascending l-start (= m from the kappa
    fold) and tiled TK at a time, exactly like the ragged schedule;
  * a scalar-prefetch array l0s[g] carries each tile's first valid degree,
    and the in-kernel recurrence loop runs l = l0s[g] .. L-1 -- the
    zero-triangle is neither stored nor executed;
  * seeds + (d_prev, d_cur) recurrence state live in VMEM; HBM traffic is
    seeds (K*J) + rhs (K*J*C2) + out (K*L*C2) with NO d-table term;
  * the contraction lane axis C2 is V*C*2 for V simultaneous transforms
    (ops.batched_rhs / ops.make_dwt_fn(batch=V) pack them), so a batch of
    rotations costs one kernel launch and re-uses each generated d-row
    V times -- the recurrence FLOPs amortize linearly in V.

Work accounting (what benchmarks/dwt_schedules.py reports):

    row-steps(onthefly) = (K/TK) * L
    row-steps(fused)    = sum_g (L - l0s[g])   (~2.4x fewer at B = 512)

VMEM per grid step (f32, TK=8, B=512): seeds/prev/cur 3*TK*J = 96 KB,
rhs TK*J*C2 = 512 KB (V=1), out TK*L*C2 = 256 KB -- far under the ~16 MB
budget, leaving headroom for V up to ~16 lanes of batching.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import I0, resolve_interpret
from .wigner_rec import _contract_row, _recurrence_step

__all__ = ["build_tile_lstarts", "dwt_fused", "idwt_fused"]


def build_tile_lstarts(l_start: np.ndarray, tk: int) -> np.ndarray:
    """Host-side ragged metadata: per cluster-tile first valid degree.

    l_start: (K,) per-cluster l-start (= m), pre-sorted ascending so tiles
    bucket uniform extents (ops.fused_metadata does the sort).  Returns
    (K // tk,) int32 -- the scalar-prefetch steering array.
    """
    K = len(l_start)
    if K % tk:
        raise ValueError(f"K={K} not divisible by tk={tk}")
    return np.asarray(l_start, np.int32).reshape(K // tk, tk).min(axis=1)


def _fused_fwd_kernel(L, l0_ref, seeds_ref, m_ref, mp_ref, cb_ref, r_ref,
                      o_ref, prev_ref, cur_ref):
    g = pl.program_id(0)
    l0 = l0_ref[g]
    seeds = seeds_ref[...]
    m = m_ref[...]            # (TK, 1)
    mp = mp_ref[...]
    cb = cb_ref[...]          # (1, J)
    prev_ref[...] = jnp.zeros_like(prev_ref)
    cur_ref[...] = jnp.zeros_like(cur_ref)
    # rows l < l0 are never visited; the true output there is zero (l < m
    # for every cluster in the tile), so a single memset covers them.
    o_ref[...] = jnp.zeros_like(o_ref)

    def body(l, _):
        row, p, c = _recurrence_step(l, m, mp, cb, prev_ref[...],
                                     cur_ref[...], seeds)
        o_ref[:, pl.ds(l, 1), :] = _contract_row(row, r_ref[...],
                                                 o_ref.dtype)[:, None, :]
        prev_ref[...] = p
        cur_ref[...] = c

    jax.lax.fori_loop(l0, jnp.int32(L), body, None)


@partial(jax.jit, static_argnames=("B", "tk", "interpret"))
def dwt_fused(seeds, m, mp, cos_beta, rhs, l0s, *, B, tk=8, interpret=None):
    """Forward fused DWT: ragged l-range + on-the-fly Wigner rows.

    seeds: (K, J); m, mp: (K,) int; cos_beta: (J,); rhs: (K, J, C2) with
    C2 = V*C*2 lanes for V batched transforms; l0s: (K // tk,) int32 tile
    l-starts (build_tile_lstarts).  Clusters must be sorted so each
    TK-tile's l-extents agree with l0s.  Returns out (K, B, C2).
    """
    interpret = resolve_interpret(interpret)
    K, J = seeds.shape
    C2 = rhs.shape[-1]
    tk = min(tk, K)
    if K % tk:
        raise ValueError(f"K={K} % tk={tk}")
    dt = seeds.dtype
    mf = m.astype(dt)[:, None]
    mpf = mp.astype(dt)[:, None]
    cb = cos_beta.astype(dt)[None, :]
    out = pl.pallas_call(
        partial(_fused_fwd_kernel, B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K // tk,),
            in_specs=[
                pl.BlockSpec((tk, J), lambda k, l0s: (k, I0)),      # seeds
                pl.BlockSpec((tk, 1), lambda k, l0s: (k, I0)),      # m
                pl.BlockSpec((tk, 1), lambda k, l0s: (k, I0)),      # mp
                pl.BlockSpec((1, J), lambda k, l0s: (I0, I0)),     # cos_beta
                pl.BlockSpec((tk, J, C2), lambda k, l0s: (k, I0, I0)),
            ],
            out_specs=pl.BlockSpec((tk, B, C2), lambda k, l0s: (k, I0, I0)),
            scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((K, B, C2), dt),
        interpret=interpret,
    )(jnp.asarray(l0s, jnp.int32), seeds, mf, mpf, cb, rhs)
    return out


def _fused_inv_kernel(L, l0_ref, seeds_ref, m_ref, mp_ref, cb_ref, l_ref,
                      o_ref, prev_ref, cur_ref):
    g = pl.program_id(0)
    l0 = l0_ref[g]
    seeds = seeds_ref[...]
    m = m_ref[...]
    mp = mp_ref[...]
    cb = cb_ref[...]
    prev_ref[...] = jnp.zeros_like(prev_ref)
    cur_ref[...] = jnp.zeros_like(cur_ref)
    o_ref[...] = jnp.zeros_like(o_ref)

    def body(l, _):
        row, p, c = _recurrence_step(l, m, mp, cb, prev_ref[...],
                                     cur_ref[...], seeds)
        # lhs rows below each cluster's l-start hold zero coefficients, so
        # starting at the tile minimum l0 drops only zero contributions.
        lhs_l = l_ref[:, pl.ds(l, 1), :]                 # (TK, 1, C2)
        o_ref[...] += row[:, :, None] * lhs_l
        prev_ref[...] = p
        cur_ref[...] = c

    jax.lax.fori_loop(l0, jnp.int32(L), body, None)


@partial(jax.jit, static_argnames=("B", "tk", "interpret"))
def idwt_fused(seeds, m, mp, cos_beta, lhs, l0s, *, B, tk=8, interpret=None):
    """Inverse fused iDWT.  lhs: (K, B, C2); returns g (K, J, C2)."""
    interpret = resolve_interpret(interpret)
    K, J = seeds.shape
    C2 = lhs.shape[-1]
    tk = min(tk, K)
    if K % tk:
        raise ValueError(f"K={K} % tk={tk}")
    dt = seeds.dtype
    mf = m.astype(dt)[:, None]
    mpf = mp.astype(dt)[:, None]
    cb = cos_beta.astype(dt)[None, :]
    out = pl.pallas_call(
        partial(_fused_inv_kernel, B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K // tk,),
            in_specs=[
                pl.BlockSpec((tk, J), lambda k, l0s: (k, I0)),
                pl.BlockSpec((tk, 1), lambda k, l0s: (k, I0)),
                pl.BlockSpec((tk, 1), lambda k, l0s: (k, I0)),
                pl.BlockSpec((1, J), lambda k, l0s: (I0, I0)),
                pl.BlockSpec((tk, B, C2), lambda k, l0s: (k, I0, I0)),
            ],
            out_specs=pl.BlockSpec((tk, J, C2), lambda k, l0s: (k, I0, I0)),
            scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((K, J, C2), dt),
        interpret=interpret,
    )(jnp.asarray(l0s, jnp.int32), seeds, mf, mpf, cb, lhs)
    return out
