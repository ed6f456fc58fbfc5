"""Pallas TPU kernel: the FUSED clustered DWT/iDWT (ragged l-range +
on-the-fly Wigner rows), the one kernel family of this package.

It joins the two levers of the paper's DWT stage, plus multi-transform
lane batching:

  * the Wigner-d rows are generated on the fly by the three-term
    recurrence (paper Eq. 2), so the table never touches HBM (~0.37 TB
    at B = 512 in f64, what pinned the paper's benchmark to a 128 GB
    node);
  * clusters are host-sorted by ascending l-start (= m from the kappa
    fold) and tiled TK at a time, and a scalar-prefetch array l0s[g]
    carries each tile's first valid degree, so the in-kernel recurrence
    loop runs l = l0s[g] .. L-1: the l < max(|m|, |m'|) zero-triangle
    (paper point P3) is neither stored nor executed;
  * seeds + (d_prev, d_cur) recurrence state live in VMEM; HBM traffic is
    seeds (K*J) + rhs (K*C2*J) + out (K*C2*L) with NO d-table term;
  * the member-row axis C2 is V*C*2 for V simultaneous transforms
    (ops.batched_rhs / ops.make_dwt_fn(batch=V) pack them), so a batch of
    rotations costs one kernel launch and re-uses each generated d-row
    V times -- the recurrence FLOPs amortize linearly in V.

Operand contract: C2-major.  The forward maps rhs (K, C2, J) to out
(K, C2, L); the inverse maps lhs (K, C2, L) to g (K, C2, J).  C2 = 16, 64
or 128 rows sit on the sublanes and J = 2B or L = B on the 128 lanes, so
under the TPU's (8, 128) tiling every block the kernels move, and every
array the grid stages hand them, is dense.

The contraction runs on the MXU, a PANEL of rows at a time.  The
recurrence loop only generates rows: row l goes to row l - base of a
(TK, P, J) VMEM panel in the plan dtype (rows below the tile's l0 are
zero).  When the panel is full, each cluster of the tile does one matrix
product: the forward writes out[k, :, base:base+P] = rhs[k] @ panel[k]^T
((C2 x J) . (J x P)), the inverse adds lhs[k, :, base:base+P] @ panel[k]
((C2 x P) . (P x J)) into its (C2, J) output.  The panels of a grid step
are unrolled, so each product's degree slice of the lanes is static.
Both products run at
``Precision.HIGHEST`` (f32 on the chip: a one-pass bf16 product would
read ~1e-3 where f32 reads ~2e-6).  The panel depth P is the whole
degree range (P = L, one product per cluster per grid step) wherever
the VMEM estimate allows, else the largest multiple of 8 dividing L that
fits (:func:`repro.kernels.autotune.panel_depth`); the streaming kernels
(streaming.py) run the same helpers with P = lchunk.

Work accounting: a grid step of tile g marches L - l0s[g] rows, so a
launch runs sum_g (L - l0s[g]) row-steps where a full-range march would
run (K/TK) * L (~2.4x more at B = 512).

VMEM per grid step (f32, TK=8): the pipeline holds two buffers of every
block it streams (seeds, rhs, out) and one of its scratch (recurrence
state, panel).  At B = 128, V = 1 (the ladder) the (TK, C2, J) rhs block
is 128 KB, the (TK, C2, L) coefficient block 64 KB, the seeds 8 KB, the
state 2*TK*J = 16 KB and the (TK, P, J) panel 1 MB.  At B = 512: rhs
TK*C2*J = 512 KB and out TK*C2*L = 256 KB per lane of V, state 64 KB,
panel TK*P*J = 4 MB per 128 rows.  Under the 12 MB default budget
(autotune.estimate_vmem_bytes) P = L up to B = 256 at every V <= 8; at
B = 512 P = 256 for V <= 2 and 128 for V = 4, and V = 8 does not fit
(two buffers of its rhs and out blocks alone take 12 MB).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import I0, resolve_interpret

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


def _recurrence_step(l, m, mp, cb, d_prev, d_cur, seeds):
    """One l-step of the three-term recurrence, shared by the fused and
    streaming kernels.  Returns (row_l, d_prev', d_cur').

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


def contract_panel(panel_ref, x_ref, o_ref, col0, *, inverse):
    """One MXU product per cluster of the tile, on C2-major operands.
    Forward: o[k, :, col0:col0+P] = x[k] @ panel[k]^T ((C2 x J) . (J x
    P)).  Inverse: o[k] += x[k, :, col0:col0+P] @ panel[k] ((C2 x P) .
    (P x J)).  col0 is static: the degree slice sits on the lanes."""
    P = panel_ref.shape[1]
    for k in range(panel_ref.shape[0]):
        if inverse:
            o_ref[k] += jnp.dot(
                x_ref[k, :, col0:col0 + P], panel_ref[k],
                precision=_HIGHEST, preferred_element_type=o_ref.dtype)
        else:
            o_ref[k, :, col0:col0 + P] = jax.lax.dot_general(
                x_ref[k], panel_ref[k], (((1,), (1,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=o_ref.dtype)


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

    # panels unrolled, so that each product's degree slice of the lanes
    # is static; the recurrence state carries from panel to panel
    for base in range(0, L, P):
        @pl.when(l0 < base + P)
        def _panel():
            fill_panel(row, jnp.maximum(l0, base), base, panel_ref)
            contract_panel(panel_ref, x_ref, o_ref, base, inverse=inverse)


def _fused_call(seeds, m, mp, cos_beta, x, l0s, *, B, tk, vmem_limit,
                inverse, interpret):
    from . import autotune   # autotune imports ops, which imports us

    interpret = resolve_interpret(interpret)
    K, J = seeds.shape
    C2 = x.shape[1]
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
    x_cols, o_cols = (B, J) if inverse else (J, B)
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
                pl.BlockSpec((tk, C2, x_cols), lambda k, l0s: (k, I0, I0)),
            ],
            out_specs=pl.BlockSpec((tk, C2, o_cols),
                                   lambda k, l0s: (k, I0, I0)),
            scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt),
                            pltpu.VMEM((tk, P, J), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((K, C2, o_cols), dt),
        interpret=interpret,
    )(jnp.asarray(l0s, jnp.int32), seeds, mf, mpf, cb, x)


@partial(jax.jit, static_argnames=("B", "tk", "vmem_limit", "interpret"))
def dwt_fused(seeds, m, mp, cos_beta, rhs, l0s, *, B, tk=8, vmem_limit=None,
              interpret=None):
    """Forward fused DWT: ragged l-range + on-the-fly Wigner rows.

    seeds: (K, J); m, mp: (K,) int; cos_beta: (J,); rhs: (K, C2, J) with
    C2 = V*C*2 member rows for V batched transforms; l0s: (K // tk,)
    int32 tile l-starts (build_tile_lstarts).  Clusters must be sorted so
    each TK-tile's l-extents agree with l0s.  vmem_limit (default
    :func:`repro.kernels.autotune.vmem_limit_bytes`) is the budget the
    panel depth is derived against.  Returns out (K, C2, B).
    """
    return _fused_call(seeds, m, mp, cos_beta, rhs, l0s, B=B, tk=tk,
                       vmem_limit=vmem_limit, inverse=False,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("B", "tk", "vmem_limit", "interpret"))
def idwt_fused(seeds, m, mp, cos_beta, lhs, l0s, *, B, tk=8, vmem_limit=None,
               interpret=None):
    """Inverse fused iDWT.  lhs: (K, C2, B), zero below each cluster's
    l-start; returns g (K, C2, J).  Arguments as in :func:`dwt_fused`."""
    return _fused_call(seeds, m, mp, cos_beta, lhs, l0s, B=B, tk=tk,
                       vmem_limit=vmem_limit, inverse=True,
                       interpret=interpret)
