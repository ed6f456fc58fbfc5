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

The contraction runs on the MXU, a PANEL of rows at a time.  The
recurrence loop only generates rows: row l goes to row l - base of a
(TK, P, J) VMEM panel in the plan dtype (rows below the tile's l0 are
zero).  When the panel is full, each cluster of the tile does one matrix
product: the forward writes out[k, base:base+P] = panel[k] @ rhs[k]
((P x J) . (J x C2)), the inverse adds panel[k]^T @ lhs[k, base:base+P]
((J x P) . (P x C2)) into its (J, C2) output.  Both products run at
``Precision.HIGHEST`` (f32 on the chip: a one-pass bf16 product would
read ~1e-3 where f32 reads ~2e-6).  The panel depth P is the whole
degree range (P = L, one product per cluster per grid step) wherever
the VMEM estimate allows, else the largest multiple of 8 dividing L that
fits (:func:`repro.kernels.autotune.panel_depth`); the streaming kernels
(streaming.py) run the same helpers with P = lchunk.

Work accounting (what benchmarks/dwt_schedules.py reports):

    row-steps(onthefly) = (K/TK) * L
    row-steps(fused)    = sum_g (L - l0s[g])   (~2.4x fewer at B = 512)

VMEM per grid step (f32, TK=8, B=512): seeds/prev/cur 3*TK*J = 96 KB,
rhs TK*J*C2 = 512 KB (V=1), out TK*L*C2 = 256 KB, panel TK*P*J = 4 MB
per 128 rows.  Under the 12 MB default budget P = L up to B = 256 at
every V <= 8; at B = 512 P = 256 for V <= 4 and 128 for V = 8.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import I0, resolve_interpret
from .wigner_rec import _recurrence_step

__all__ = ["build_tile_lstarts", "dwt_fused", "idwt_fused"]

_HIGHEST = jax.lax.Precision.HIGHEST


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


def march(l, m, mp, cb, seeds, prev_ref, cur_ref):
    """One recurrence step against the (d_prev, d_cur) state refs;
    returns the (TK, J) Wigner row of degree l."""
    row, p, c = _recurrence_step(l, m, mp, cb, prev_ref[...], cur_ref[...],
                                 seeds)
    prev_ref[...] = p
    cur_ref[...] = c
    return row


def fill_panel(row, lo, base, panel_ref):
    """Generate rows l = lo .. base+P-1 with ``row(l)`` into panel rows
    l - base; rows below lo are zero."""
    P = panel_ref.shape[1]

    @pl.when(lo > base)
    def _zero():
        panel_ref[...] = jnp.zeros_like(panel_ref)

    def body(l, _):
        panel_ref[:, pl.ds(l - base, 1), :] = (
            row(l).astype(panel_ref.dtype)[:, None, :])

    jax.lax.fori_loop(lo, base + P, body, None)


def contract_panel(panel_ref, x_ref, o_ref, row0, *, inverse):
    """One MXU product per cluster of the tile.  Forward: o[k, row0:row0+P]
    = panel[k] @ x[k] ((P x J) . (J x C2)).  Inverse: o[k] +=
    panel[k]^T @ x[k, row0:row0+P] ((J x P) . (P x C2))."""
    P = panel_ref.shape[1]
    for k in range(panel_ref.shape[0]):
        if inverse:
            o_ref[k] += jax.lax.dot_general(
                panel_ref[k], x_ref[k, pl.ds(row0, P), :],
                (((0,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=o_ref.dtype)
        else:
            o_ref[k, pl.ds(row0, P), :] = jnp.dot(
                panel_ref[k], x_ref[k], precision=_HIGHEST,
                preferred_element_type=o_ref.dtype)


def _fused_kernel(L, P, inverse, l0_ref, seeds_ref, m_ref, mp_ref, cb_ref,
                  x_ref, o_ref, prev_ref, cur_ref, panel_ref):
    l0 = l0_ref[pl.program_id(0)]
    seeds = seeds_ref[...]
    m = m_ref[...]            # (TK, 1)
    mp = mp_ref[...]
    cb = cb_ref[...]          # (1, J)
    prev_ref[...] = jnp.zeros_like(prev_ref)
    cur_ref[...] = jnp.zeros_like(cur_ref)
    # the inverse accumulates over panels; the forward's panels below l0
    # are never visited, and its true output there is zero (l < m for
    # every cluster in the tile).  With one panel (P = L) the forward
    # product writes every row.
    if inverse or P < L:
        o_ref[...] = jnp.zeros_like(o_ref)

    def row(l):
        return march(l, m, mp, cb, seeds, prev_ref, cur_ref)

    def panel(i, _):
        base = pl.multiple_of(i * P, P)
        fill_panel(row, jnp.maximum(l0, base), base, panel_ref)
        contract_panel(panel_ref, x_ref, o_ref, base, inverse=inverse)

    first = jax.lax.div(l0, jnp.int32(P)) if P < L else I0   # l0 >= 0
    jax.lax.fori_loop(first, jnp.int32(L // P), panel, None)


def _fused_call(seeds, m, mp, cos_beta, x, l0s, *, B, tk, vmem_limit,
                inverse, interpret):
    from . import autotune   # autotune imports ops, which imports us

    interpret = resolve_interpret(interpret)
    K, J = seeds.shape
    C2 = x.shape[-1]
    tk = min(tk, K)
    if K % tk:
        raise ValueError(f"K={K} % tk={tk}")
    dt = seeds.dtype
    P = autotune.panel_depth(L=B, J=J, C2=C2, tk=tk,
                             itemsize=jnp.dtype(dt).itemsize,
                             limit=vmem_limit)
    mf = m.astype(dt)[:, None]
    mpf = mp.astype(dt)[:, None]
    cb = cos_beta.astype(dt)[None, :]
    x_rows, o_rows = (B, J) if inverse else (J, B)
    return pl.pallas_call(
        partial(_fused_kernel, B, P, inverse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K // tk,),
            in_specs=[
                pl.BlockSpec((tk, J), lambda k, l0s: (k, I0)),      # seeds
                pl.BlockSpec((tk, 1), lambda k, l0s: (k, I0)),      # m
                pl.BlockSpec((tk, 1), lambda k, l0s: (k, I0)),      # mp
                pl.BlockSpec((1, J), lambda k, l0s: (I0, I0)),     # cos_beta
                pl.BlockSpec((tk, x_rows, C2), lambda k, l0s: (k, I0, I0)),
            ],
            out_specs=pl.BlockSpec((tk, o_rows, C2),
                                   lambda k, l0s: (k, I0, I0)),
            scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt),
                            pltpu.VMEM((tk, P, J), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((K, o_rows, C2), dt),
        interpret=interpret,
    )(jnp.asarray(l0s, jnp.int32), seeds, mf, mpf, cb, x)


@partial(jax.jit, static_argnames=("B", "tk", "vmem_limit", "interpret"))
def dwt_fused(seeds, m, mp, cos_beta, rhs, l0s, *, B, tk=8, vmem_limit=None,
              interpret=None):
    """Forward fused DWT: ragged l-range + on-the-fly Wigner rows.

    seeds: (K, J); m, mp: (K,) int; cos_beta: (J,); rhs: (K, J, C2) with
    C2 = V*C*2 lanes for V batched transforms; l0s: (K // tk,) int32 tile
    l-starts (build_tile_lstarts).  Clusters must be sorted so each
    TK-tile's l-extents agree with l0s.  vmem_limit (default
    :func:`repro.kernels.autotune.vmem_limit_bytes`) is the budget the
    panel depth is derived against.  Returns out (K, B, C2).
    """
    return _fused_call(seeds, m, mp, cos_beta, rhs, l0s, B=B, tk=tk,
                       vmem_limit=vmem_limit, inverse=False,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("B", "tk", "vmem_limit", "interpret"))
def idwt_fused(seeds, m, mp, cos_beta, lhs, l0s, *, B, tk=8, vmem_limit=None,
               interpret=None):
    """Inverse fused iDWT.  lhs: (K, B, C2), zero below each cluster's
    l-start; returns g (K, J, C2).  Arguments as in :func:`dwt_fused`."""
    return _fused_call(seeds, m, mp, cos_beta, lhs, l0s, B=B, tk=tk,
                       vmem_limit=vmem_limit, inverse=True,
                       interpret=interpret)
