"""Pallas TPU kernels for the framework's compute hot-spots.

  dwt.py               clustered DWT/iDWT (dense + ragged work-list grids)
  wigner_rec.py        DWT fused with the on-the-fly Wigner-d recurrence
  dwt_fused.py         BOTH levers at once: ragged l-range (zero-triangle
                       skipped via scalar-prefetch l0s) + on-the-fly rows
                       (no d-table in HBM) + V-wide transform batching
  streaming.py         the fused family at paper-scale B: l-chunked
                       coefficient staging (HBM-resident stacks, two-row
                       recurrence windows) + bf16 storage precision
  peaks.py             grid_peaks: per-lane maximum and first argmax of a
                       stack of correlation grids (a bank query's peak
                       search, so no grid leaves the device)
  folded_attention.py  causal flash attention on the paper's folded grid
  autotune.py          measured (tk, tl, tj, V) sweep, on-disk cache
  ops.py               jit'd wrappers (auto interpret-mode on CPU)
  runtime.py           default_interpret() shared by every wrapper
  ref.py               pure-jnp oracles

Which schedule when -- plan it, don't pick it
---------------------------------------------

Schedule choice is a PLANNER decision: ``repro.plan(B, impl="auto",
V="auto")`` resolves impl, lane width V, and tiles through this
package's autotuner (statically via the VMEM-guard estimator, or the
measured on-disk-cached sweep under ``tune="measure"`` /
``$REPRO_PLAN_TUNE=measure``), then owns the resulting kernel closures
for every executor (single, V-lane batch, sharded).  ``make_dwt_fn`` /
``make_idwt_fn`` below stay as the kernel-level binding the planner
(and kernel tests/benchmarks) build on.  What the planner is choosing
between (``impl=...`` forces one):

  dense     Simplest; pads every cluster to the full l-range and streams
            the whole d-table from HBM.  Only competitive at tiny B or
            when the table is already resident and B <= ~64.
  ragged    Paper P3: skips the l < max(|m|,|m'|) zero-triangle blocks
            (~2.4x fewer MXU blocks at B = 512) but still reads the
            visited d-blocks from HBM.  Best when VMEM is too tight for
            the recurrence state or d is cheap to keep (small B, many
            reuses per table build).  (Forward only -- planned inverses
            fall back to the dense grid.)
  onthefly  No d-table anywhere (seeds + three-term recurrence in VMEM);
            HBM traffic drops by ~L/2 vs dense.  Executes the full l-range
            per cluster, so it pays the zero-triangle in compute.  Best
            at large B when clusters are unsorted.
  fused     onthefly + the ragged skip: host-sorted clusters, per-tile
            scalar-prefetch l0, recurrence starts at l0.  Strictly fewer
            row-steps than onthefly AND no d-table term -- what
            impl="auto" resolves to (statically) for every B.  batch=V
            packs V transforms onto the lane axis (C2 = V*C*2): one
            launch, each generated d-row reused V times
            (Transform.forward_batch / inverse_batch).  Rows go to a
            (TK, P, J) VMEM panel, contracted by one MXU product per
            cluster (P = B where VMEM allows: autotune.panel_depth).  With
            ``lchunk``/``precision="bf16"`` the planner swaps in the
            STREAMING members (streaming.py): only a (TK, lchunk, C2)
            coefficient tile is VMEM-live (the stack stays HBM-resident,
            staged through double-buffered slots), the recurrence resumes
            from per-chunk two-row windows, and bf16 halves the stored
            window table + rounds the rows to bf16 while state, panel
            and accumulation stay in the plan dtype; the chunk is the
            panel.  Keyed by /L{lchunk}/
            P{precision}; auto-engaged when no monolithic V fits VMEM.
  reference Planner-only pseudo-schedule: the pure-jnp einsum path
            (differentiable, runs anywhere) -- the correctness oracle.

VMEM budgets (f32, TK = 8): dense/ragged hold a (TK, TL, TJ) d-block
(2 MB at 8x128x512) + rhs + out; the recurrence schedules hold seeds +
2 state rows (3*TK*J) + rhs (TK*J*C2) + out (TK*L*C2) -- ~1 MB at B = 512
V = 1, leaving lane-batching headroom to V ~ 16 under the ~16 MB ceiling.
``V="auto"`` picks the widest lane packing whose estimate fits
$REPRO_VMEM_BYTES (autotune.vmem_limit_bytes).

Tile choice is measured, not guessed: kernels/autotune.py sweeps the
divisor-constrained candidates per (B, dtype, backend, impl, V,
vmem-limit, n_shards) and memoizes winners in $REPRO_AUTOTUNE_CACHE
(default ~/.cache/repro/autotune.json).  Mesh plans tune the PER-DEVICE
cluster shard (kloc = K/n_shards) under an /S{n_shards} cache-key
segment, and the distributed batch execution mode -- serial V-chunk
launches vs the DistExecutor's double-buffered overlap pipeline -- is
resolved by autotune.static_overlap / autotune_overlap under an
/O{mode} segment (docs/ARCHITECTURE.md spells out the full key
grammar).  benchmarks/dwt_schedules.py prints the block/HBM accounting
behind the guidance above, and benchmarks/planner.py smokes the plan
build/cache/executor path.
"""
from . import (autotune, dwt, dwt_fused, folded_attention, ops, peaks,  # noqa: F401
               ref, runtime, streaming, wigner_rec)
