"""Pallas TPU kernels: l-chunked STREAMING fused DWT/iDWT for paper-scale B.

The monolithic fused kernel (dwt_fused.py) holds a cluster-tile's ENTIRE
l-range in VMEM per grid step: the forward out tile is (TK, L, C2) and the
inverse coefficient tile (TK, L, C2).  At the paper's "accuracy- and
memory-critical bandwidth 512" with V-lane packing (C2 = V*C*2) that tile
alone is TK*512*C2*4 bytes -- 2 MB at V = 1 and 16 MB at V = 8, past the
per-core VMEM budget exactly where lane packing matters most.  This module
splits the degree axis into nL = L/lchunk chunks so only an (TK, lchunk,
C2) coefficient tile is ever VMEM-live:

  * coefficient blocks stay HBM-RESIDENT: the (K, L, C2) stack is carried
    in HBM and Pallas stages one (TK, lchunk, C2) tile per grid step into
    double-buffered VMEM slots (the same two-slot overlap pattern the
    DistExecutor pipeline uses per V-chunk, here at the DMA level inside
    one kernel -- chunk i's tile contracts while chunk i+1's tile streams);
  * the on-the-fly recurrence carries only a TWO-ROW SEED WINDOW per
    chunk: :func:`build_windows` marches the three-term recurrence once
    (same jnp ops as the kernel, so every chunk generates the rows the
    monolithic kernel generates, bit for bit) and emits the (d_{l-1}, d_l)
    state at each chunk boundary, a (nL, 2, K, J) table that is
    lchunk/2 x smaller than the full (K, L, J) Wigner table;
  * the chunk is the PANEL of the shared MXU contraction
    (dwt_fused.fill_panel / contract_panel, P = lchunk): a grid step
    generates its chunk's rows into a (TK, lchunk, J) VMEM panel, then
    runs one matrix product per cluster;
  * the ragged zero-triangle skip survives chunking: each (tile, chunk)
    grid step runs l = max(l0s[g], lc*lchunk) .. (lc+1)*lchunk, so chunks
    entirely below a tile's l-start cost one memset (forward) or nothing
    (inverse): no recurrence step and no product;
  * mixed precision (``precision="bf16"``): bfloat16 is a STORAGE format,
    not a compute format -- the HBM-resident window table is stored bf16
    (halving the largest new paper-scale object) and each generated d-row
    is rounded to bf16 before it enters the panel, while the in-kernel
    recurrence state, the panel and the accumulation stay in the plan
    dtype (>= fp32).  Rounding therefore happens nL + 1 times per value
    (once per chunk boundary + once per row), not once per recurrence
    step: carrying the state itself in bf16 compounds rounding through
    all L steps and measures ~27x worse at B = 64.  The resulting
    per-(B, precision) error is tabulated by benchmarks/error_table.py
    and gated in kernels.autotune.PRECISION_ERROR_BOUNDS.

Grid layout: (K/TK, nL) with the chunk axis innermost.  The forward rhs
block index is constant over lc (the tile stays VMEM-resident across a
cluster-tile's chunks); the inverse output block revisits (K-indexed, lc
ignored) and accumulates one product per chunk -- initialization happens
at lc == 0.  Every forward output element is one product over J, as in
the monolithic kernel, so the forward and the lchunk = L inverse are
bitwise equal to it in fp32/f64 (but for lchunk = 1 under XLA's CPU
backend, which sums a one-row product in another order); an inverse at
lchunk < L sums nL chunk products where the monolithic kernel sums one,
so it agrees to the dtype's rounding, and bit for bit only with a
monolithic kernel whose panel is lchunk rows deep.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dwt_fused import _recurrence_step, contract_panel, fill_panel, march
from .runtime import I0, resolve_interpret

__all__ = ["build_windows", "dwt_streaming", "idwt_streaming",
           "check_lchunk"]


def check_lchunk(L: int, lchunk: int, *, tiled: bool = False) -> int:
    """Validate an l-chunk size: 1 <= lchunk <= L and lchunk | L (the
    chunk grid must tile the degree axis exactly).  ``tiled`` adds the
    TPU's rule for the (tk, lchunk, C2) blocks: lchunk is a multiple of
    the 8-row sublane tile, or the whole degree axis.  Interpret mode has
    no tiling, so tests there may use smaller chunks."""
    lchunk = int(lchunk)
    if not 1 <= lchunk <= L:
        raise ValueError(f"lchunk={lchunk} outside [1, L={L}]")
    if L % lchunk:
        raise ValueError(f"lchunk={lchunk} does not divide L={L}")
    if tiled and lchunk % 8 and lchunk != L:
        raise ValueError(
            f"lchunk={lchunk} is neither a multiple of 8 nor L={L}: the "
            f"TPU compiler refuses its (tk, lchunk, C2) blocks")
    return lchunk


@partial(jax.jit, static_argnames=("L", "lchunk", "state_dtype"))
def build_windows(seeds, m, mp, cos_beta, *, L, lchunk, state_dtype=None):
    """Chunk-boundary recurrence windows: (nL, 2, K, J).

    windows[c] holds the (d_prev, d_cur) three-term-recurrence state at
    the START of degree l = c*lchunk, marched from l = 0 with the exact
    jnp ops the streaming kernel body uses (clusters activate via their
    seed row at l = m; the state is pinned to zero below).  windows[0] is
    zero -- the kernel's seed logic performs every activation, so chunk 0
    needs no history.  This is the only Wigner state that ever returns to
    HBM: nL * 2 rows per cluster instead of the L-row dense table, i.e.
    an lchunk/2 x smaller footprint, halved again under bf16 storage.

    m, mp, cos_beta must already be the broadcast-ready kernel operands
    ((K, 1), (K, 1), (1, J)) in the compute dtype; state_dtype (default:
    the compute dtype) selects the STORED precision -- the march itself
    always runs in the compute dtype and each boundary snapshot is
    rounded exactly once on store.
    """
    lchunk = check_lchunk(L, lchunk)
    nL = L // lchunk
    sdt = seeds.dtype if state_dtype is None else jnp.dtype(state_dtype)
    K, J = seeds.shape

    # One fori_loop over every step, with boundary states scattered into
    # slot (l+1)/lchunk (non-boundary steps hit the dummy slot nL).  A
    # single uniform loop matters: per-chunk loops of length 1 get
    # unrolled and FMA-fused differently by XLA, breaking the bitwise
    # match with the kernel's own multi-step fori_loop.
    def step(l, carry):
        wins, prev, cur = carry
        _, p, c = _recurrence_step(l, m, mp, cos_beta, prev, cur, seeds)
        idx = jnp.where((l + 1) % lchunk == 0, (l + 1) // lchunk, nL)
        wins = jax.lax.dynamic_update_slice(
            wins, jnp.stack([p, c]).astype(sdt)[None], (idx, 0, 0, 0))
        return wins, p, c

    wins = jnp.zeros((nL + 1, 2, K, J), sdt)
    # boundaries past (nL-1)*lchunk are never read; stop the march there.
    cz = jnp.zeros((K, J), cos_beta.dtype)
    wins, _, _ = jax.lax.fori_loop(0, (nL - 1) * lchunk, step,
                                   (wins, cz, cz))
    return wins[:nL]


def _stream_kernel(lchunk, row_dtype, inverse, l0_ref, seeds_ref, m_ref,
                   mp_ref, cb_ref, w_ref, x_ref, o_ref, prev_ref, cur_ref,
                   panel_ref):
    g = pl.program_id(0)
    lc = pl.program_id(1)
    base = lc * lchunk
    lo = jnp.maximum(l0_ref[g], base)
    live = lo < base + lchunk       # some row of the chunk is at or past l0
    seeds = seeds_ref[...]
    m = m_ref[...]            # (TK, 1)
    mp = mp_ref[...]
    cb = cb_ref[...]          # (1, J)
    prev_ref[...] = w_ref[0, 0].astype(prev_ref.dtype)
    cur_ref[...] = w_ref[0, 1].astype(cur_ref.dtype)

    if inverse:
        # the output block revisits across the (innermost) chunk axis:
        # initialize once, then every chunk adds its product
        @pl.when(lc == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
    else:
        @pl.when(jnp.logical_not(live))
        def _below():            # a chunk below the tile's l-start
            o_ref[...] = jnp.zeros_like(o_ref)

    # the state stays in the plan dtype; only the row is rounded to the
    # storage precision (a no-op for fp32/f64)
    def row(l):
        return march(l, m, mp, cb, seeds, prev_ref, cur_ref).astype(row_dtype)

    @pl.when(live)
    def _chunk():
        fill_panel(row, lo, base, panel_ref)
        contract_panel(panel_ref, x_ref, o_ref, 0, inverse=inverse)


def _stream_call(seeds, m, mp, cos_beta, x, l0s, windows, *, B, tk, lchunk,
                 precision, inverse, interpret):
    interpret = resolve_interpret(interpret)
    lchunk = check_lchunk(B, lchunk, tiled=not interpret)
    K, J = seeds.shape
    C2 = x.shape[-1]
    tk = min(tk, K)
    if K % tk:
        raise ValueError(f"K={K} % tk={tk}")
    nL = B // lchunk
    if windows.shape != (nL, 2, K, J):
        raise ValueError(f"windows {windows.shape} != {(nL, 2, K, J)}")
    dt = seeds.dtype
    sdt = jnp.bfloat16 if precision == "bf16" else dt
    mf = m.astype(dt)[:, None]
    mpf = mp.astype(dt)[:, None]
    cb = cos_beta.astype(dt)[None, :]
    if inverse:     # coefficients staged chunk by chunk; output revisited
        x_spec = pl.BlockSpec((tk, lchunk, C2),
                              lambda k, lc, l0s: (k, lc, I0))
        o_spec = pl.BlockSpec((tk, J, C2), lambda k, lc, l0s: (k, I0, I0))
        o_rows = J
    else:           # the rhs tile stays resident across a tile's chunks
        x_spec = pl.BlockSpec((tk, J, C2), lambda k, lc, l0s: (k, I0, I0))
        o_spec = pl.BlockSpec((tk, lchunk, C2),
                              lambda k, lc, l0s: (k, lc, I0))
        o_rows = B
    return pl.pallas_call(
        partial(_stream_kernel, lchunk, sdt, inverse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K // tk, nL),
            in_specs=[
                pl.BlockSpec((tk, J), lambda k, lc, l0s: (k, I0)),   # seeds
                pl.BlockSpec((tk, 1), lambda k, lc, l0s: (k, I0)),   # m
                pl.BlockSpec((tk, 1), lambda k, lc, l0s: (k, I0)),   # mp
                pl.BlockSpec((1, J), lambda k, lc, l0s: (I0, I0)),  # cos_beta
                pl.BlockSpec((1, 2, tk, J),
                             lambda k, lc, l0s: (lc, I0, k, I0)),  # windows
                x_spec,
            ],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((tk, J), dt), pltpu.VMEM((tk, J), dt),
                            pltpu.VMEM((tk, lchunk, J), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((K, o_rows, C2), x.dtype),
        interpret=interpret,
    )(jnp.asarray(l0s, jnp.int32), seeds, mf, mpf, cb,
      windows.astype(sdt), x)


@partial(jax.jit, static_argnames=("B", "tk", "lchunk", "precision",
                                   "interpret"))
def dwt_streaming(seeds, m, mp, cos_beta, rhs, l0s, windows, *, B, tk=8,
                  lchunk=8, precision="fp32", interpret=None):
    """Forward fused DWT with an l-chunked streaming schedule.

    Same contract as :func:`repro.kernels.dwt_fused.dwt_fused` plus:
    windows -- the (nL, 2, K, J) chunk-boundary state from
    :func:`build_windows` (in the storage dtype); lchunk -- chunk length
    (must divide B), which is also the panel depth; precision -- "fp32"
    (everything in the plan dtype; bitwise-equal to the monolithic
    kernel) or "bf16" (bf16 window storage + bf16-rounded rows; recurrence
    state and accumulation stay in the plan dtype).  Returns out
    (K, B, C2) in the rhs dtype.
    """
    return _stream_call(seeds, m, mp, cos_beta, rhs, l0s, windows, B=B,
                        tk=tk, lchunk=lchunk, precision=precision,
                        inverse=False, interpret=interpret)


@partial(jax.jit, static_argnames=("B", "tk", "lchunk", "precision",
                                   "interpret"))
def idwt_streaming(seeds, m, mp, cos_beta, lhs, l0s, windows, *, B, tk=8,
                   lchunk=8, precision="fp32", interpret=None):
    """Inverse fused iDWT, l-chunked: the (K, B, C2) coefficient stack
    stays HBM-resident and is staged chunk-by-chunk into (tk, lchunk, C2)
    VMEM tiles; see :func:`dwt_streaming`.  Returns g (K, J, C2)."""
    return _stream_call(seeds, m, mp, cos_beta, lhs, l0s, windows, B=B,
                        tk=tk, lchunk=lchunk, precision=precision,
                        inverse=True, interpret=interpret)
