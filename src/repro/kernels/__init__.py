"""Pallas TPU kernels for the framework's compute hot-spots.

  dwt_fused.py         the fused clustered DWT/iDWT: ragged l-range
                       (zero-triangle skipped via scalar-prefetch l0s) +
                       on-the-fly Wigner rows (no d-table in HBM) +
                       V-wide transform batching, rows contracted a VMEM
                       panel at a time on the MXU
  streaming.py         the same family at paper-scale B: l-chunked
                       coefficient staging (HBM-resident stacks, two-row
                       recurrence windows) + bf16 storage precision
  peaks.py             grid_peaks: per-lane maximum and first argmax of a
                       stack of correlation grids (a bank query's peak
                       search, so no grid leaves the device)
  folded_attention.py  causal flash attention on the paper's folded grid
  autotune.py          VMEM/HBM estimators, measured (tk, V) sweep,
                       on-disk cache
  ops.py               jit'd wrappers (auto interpret-mode on CPU)
  runtime.py           default_interpret() shared by every wrapper
  ref.py               pure-jnp oracles

One kernel family, planned
--------------------------

``repro.plan(B, V="auto")`` resolves the lane width V, the cluster tile
tk and the family member through this package's autotuner (statically
via the VMEM-guard estimator, or the measured on-disk-cached sweep under
``tune="measure"`` / ``$REPRO_PLAN_TUNE=measure``), then owns the
resulting kernel closures for every executor (single, V-lane batch,
sharded).  ``make_dwt_fn`` / ``make_idwt_fn`` below stay as the
kernel-level binding the planner (and kernel tests/benchmarks) build on.
The members of the family:

  monolithic  dwt_fused.py: host-sorted clusters, per-tile
              scalar-prefetch l0, recurrence starts at l0; batch=V packs
              V transforms onto the lane axis (C2 = V*C*2): one launch,
              each generated d-row reused V times
              (Transform.forward_batch / inverse_batch).  Rows go to a
              (TK, P, J) VMEM panel, contracted by one MXU product per
              cluster (P = B where VMEM allows: autotune.panel_depth).
  streaming   streaming.py, with ``lchunk`` set: only a (TK, lchunk, C2)
              coefficient tile is VMEM-live (the stack stays
              HBM-resident, staged through double-buffered slots), the
              recurrence resumes from per-chunk two-row windows, and the
              chunk is the panel.  Auto-engaged when no monolithic V
              fits VMEM.
  bf16        the streaming kernels with ``precision="bf16"``: the stored
              window table and the rows are rounded to bf16 while state,
              panel and accumulation stay in the plan dtype.  Keyed by
              /L{lchunk}/P{precision}.
  reference   planner-only pseudo-schedule (``impl="reference"``): the
              pure-jnp einsum path (differentiable, runs anywhere) -- the
              correctness oracle.

VMEM budget (f32, TK = 8): seeds + 2 state rows (3*TK*J) + rhs (TK*J*C2)
+ out (TK*L*C2) + the (TK, P, J) panel.  ``V="auto"`` picks the widest
lane packing whose estimate fits $REPRO_VMEM_BYTES
(autotune.vmem_limit_bytes).

Tile choice can be measured: kernels/autotune.py sweeps the
divisor-constrained tk candidates per (B, dtype, backend, V, vmem-limit,
n_shards) and memoizes winners in $REPRO_AUTOTUNE_CACHE (default
~/.cache/repro/autotune.json).  Mesh plans tune the PER-DEVICE cluster
shard (kloc = K/n_shards) under an /S{n_shards} cache-key segment, and
the distributed batch execution mode -- serial V-chunk launches vs the
DistExecutor's double-buffered overlap pipeline -- is resolved by
autotune.static_overlap / autotune_overlap under an /O{mode} segment
(docs/ARCHITECTURE.md spells out the full key grammar).
benchmarks/planner.py smokes the plan build/cache/executor path.
"""
from . import (autotune, dwt_fused, folded_attention, ops, peaks,  # noqa: F401
               ref, runtime, streaming)
