"""Pallas TPU kernel: DWT with the Wigner-d table computed ON THE FLY.

The paper (like Kostelec-Rockmore's SOFT) precomputes the Wigner-d matrices
-- at B = 512 that table is ~0.37 TB in f64 and pinned their benchmark to a
128 GB RAM node.  This kernel is the recompute-over-store adaptation for
TPU: each grid step seeds the three-term recurrence (paper Eq. 2) in VMEM
and folds each degree-l row into the contraction the moment it exists, so
the table never touches HBM.

    HBM traffic:  K*J*(C2 + 2) + K*L*C2   (rhs + seeds + out)
    vs dense DWT: K*L*J + K*J*C2 + K*L*C2 (the d-table dominates)

i.e. the memory-roofline term drops by ~L/2 (=256x at B=512) while compute
gains only the ~6 recurrence FLOPs per (k, j, l) on top of the 2*C2 matmul
FLOPs -- the kernel flips the DWT from memory-bound to compute-bound
(EXPERIMENTS.md 'soft hillclimb' measures both terms).

Layout per grid step (TK clusters):
  seeds (TK, J)   f32   recurrence seed d(m, m, m')
  mcol  (TK, 1)   f32   m   (l-start; from the kappa fold, integer data)
  mpcol (TK, 1)   f32   m'
  rhs   (TK, J, C2)     DWT right-hand side
  out   (TK, L, C2)     written row-by-row at degree l (dynamic store)
Recurrence state (d_prev, d_cur): (TK, J) VMEM scratch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import I0, resolve_interpret

__all__ = ["dwt_onthefly", "idwt_onthefly"]


def _recurrence_step(l, m, mp, cb, d_prev, d_cur, seeds):
    """One l-step shared by both kernels.  Returns (row_l, d_prev', d_cur').

    row_l is the valid (zero-masked below l = m) Wigner-d row for degree l.
    """
    lf = l.astype(d_cur.dtype)
    d_cur = jnp.where(m == lf, seeds, d_cur)
    active = m <= lf
    row = jnp.where(active, d_cur, 0.0)

    lp1 = lf + 1.0
    den = jax.lax.rsqrt(jnp.maximum((lp1**2 - m**2) * (lp1**2 - mp**2), 1.0))
    A = lp1 * (2.0 * lf + 1.0) * den
    safe_l = jnp.maximum(lf, 1.0)
    mu = jnp.where(lf > 0, m * mp / (safe_l * lp1), 0.0)
    C = jnp.where(lf > 0,
                  lp1 * jnp.sqrt(jnp.maximum((lf**2 - m**2) * (lf**2 - mp**2),
                                             0.0)) * den / safe_l,
                  0.0)
    d_next = A * (cb - mu) * d_cur - C * d_prev
    d_prev_new = jnp.where(active, d_cur, 0.0)
    d_cur_new = jnp.where(active, d_next, 0.0)
    return row, d_prev_new, d_cur_new


def _contract_row(row, rhs, out_dtype):
    """Fold one generated degree-row into the forward contraction:
    out[k, c] = sum_j row[k, j] * rhs[k, j, c].  A broadcast multiply and
    a reduction over J, which Mosaic lowers; it refuses the batched
    mat-vec einsum ("kj,kjc->kc").  The fused family contracts a panel
    of rows on the MXU instead (dwt_fused.contract_panel)."""
    # cast before the broadcast: Mosaic has no bf16 (K, J) -> (K, J, 1)
    return jnp.sum(row.astype(out_dtype)[:, :, None] * rhs.astype(out_dtype),
                   axis=1)


def _fwd_kernel(L, seeds_ref, m_ref, mp_ref, cb_ref, r_ref, o_ref,
                prev_ref, cur_ref):
    seeds = seeds_ref[...]
    m = m_ref[...]            # (TK, 1)
    mp = mp_ref[...]
    cb = cb_ref[...]          # (1, J)
    prev_ref[...] = jnp.zeros_like(prev_ref)
    cur_ref[...] = jnp.zeros_like(cur_ref)

    def body(l, _):
        row, p, c = _recurrence_step(l, m, mp, cb, prev_ref[...],
                                     cur_ref[...], seeds)
        o_ref[:, pl.ds(l, 1), :] = _contract_row(row, r_ref[...],
                                                 o_ref.dtype)[:, None, :]
        prev_ref[...] = p
        cur_ref[...] = c

    jax.lax.fori_loop(I0, jnp.int32(L), body, None)


@partial(jax.jit, static_argnames=("B", "tk", "interpret"))
def dwt_onthefly(seeds, m, mp, cos_beta, rhs, *, B, tk=8, interpret=None):
    """Forward DWT without a materialized Wigner table.

    seeds: (K, J) f32; m, mp: (K,) int; cos_beta: (J,); rhs: (K, J, C2).
    Returns out (K, B, C2).
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
        partial(_fwd_kernel, B),
        grid=(K // tk,),
        in_specs=[
            pl.BlockSpec((tk, J), lambda k: (k, I0)),    # seeds
            pl.BlockSpec((tk, 1), lambda k: (k, I0)),    # m
            pl.BlockSpec((tk, 1), lambda k: (k, I0)),    # mp
            pl.BlockSpec((1, J), lambda k: (I0, I0)),   # cos_beta
            pl.BlockSpec((tk, J, C2), lambda k: (k, I0, I0)),
        ],
        out_specs=pl.BlockSpec((tk, B, C2), lambda k: (k, I0, I0)),
        out_shape=jax.ShapeDtypeStruct((K, B, C2), dt),
        scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt)],
        interpret=interpret,
    )(seeds, mf, mpf, cb, rhs)
    return out


def _inv_kernel(L, seeds_ref, m_ref, mp_ref, cb_ref, l_ref, o_ref,
                prev_ref, cur_ref):
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
        # g[k, j, c] += row[k, j] * lhs[k, l, c]
        lhs_l = l_ref[:, pl.ds(l, 1), :]                 # (TK, 1, C2)
        o_ref[...] += row[:, :, None] * lhs_l
        prev_ref[...] = p
        cur_ref[...] = c

    jax.lax.fori_loop(I0, jnp.int32(L), body, None)


@partial(jax.jit, static_argnames=("B", "tk", "interpret"))
def idwt_onthefly(seeds, m, mp, cos_beta, lhs, *, B, tk=8, interpret=None):
    """Inverse DWT without a materialized Wigner table.

    lhs: (K, B, C2); returns g (K, J, C2).
    """
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
        partial(_inv_kernel, B),
        grid=(K // tk,),
        in_specs=[
            pl.BlockSpec((tk, J), lambda k: (k, I0)),
            pl.BlockSpec((tk, 1), lambda k: (k, I0)),
            pl.BlockSpec((tk, 1), lambda k: (k, I0)),
            pl.BlockSpec((1, J), lambda k: (I0, I0)),
            pl.BlockSpec((tk, B, C2), lambda k: (k, I0, I0)),
        ],
        out_specs=pl.BlockSpec((tk, J, C2), lambda k: (k, I0, I0)),
        out_shape=jax.ShapeDtypeStruct((K, J, C2), dt),
        scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt)],
        interpret=interpret,
    )(seeds, mf, mpf, cb, lhs)
    return out
