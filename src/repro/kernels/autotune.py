"""Measured tile autotuner for the fused clustered-DWT kernel family.

OpenFFT's lesson (arXiv:1501.07350): an exhaustive-but-cheap measured sweep
over decompositions is what turns a parallel transform design into actual
speedup.  This module times real kernel launches for a small candidate set
of (tk, V) tilings and memoizes the winner on disk keyed by
(B, dtype, backend, V, vmem_limit, n_shards, overlap, lchunk,
precision) -- one sweep
per machine/shape/mesh-decomposition, then every subsequent make_dwt_fn
call reads the cache.  n_shards > 1 tunes the per-device cluster shard
of a mesh plan (see repro.plan: mesh plans resolve their schedule
through this key); the /O{mode} key segment separates schedules timed
under the double-buffered overlap pipeline from serial ones, and
:func:`autotune_overlap` / :func:`static_overlap` resolve which mode a
mesh plan's batch executors run (measured on the real mesh, or the
static n_shards > 1 heuristic).

    from repro.kernels import autotune
    cfg = autotune.autotune_dwt(plan)       # {'tk': ..., 'V': ..., ...}
    dwt_fn = autotune.tuned_dwt_fn(plan)    # ready to use

Cache location: $REPRO_AUTOTUNE_CACHE, else ~/.cache/repro/autotune.json.
Delete the file (or pass refresh=True) to re-measure after a toolchain or
hardware change.  Candidate cluster tiles divide K; V candidates pack V
transforms onto the lane axis and are scored by *per-transform* time.
"""
from __future__ import annotations

import json
import os
import pathlib

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs

from . import ops

__all__ = ["autotune_dwt", "autotune_overlap", "static_overlap",
           "static_precision", "static_lchunk", "tuned_dwt_fn",
           "tuned_idwt_fn", "cache_path", "candidate_tiles",
           "estimate_vmem_bytes", "estimate_hbm_bytes",
           "estimate_live_coeff_bytes", "estimate_host_plan_bytes",
           "panel_depth", "vmem_limit_bytes", "PRECISIONS",
           "PRECISION_ERROR_BOUNDS", "PRECISION_BOUND_EXTRAPOLATED",
           "FP32_ROUNDTRIP_BOUNDS"]

_DEF_CACHE = "~/.cache/repro/autotune.json"

# Conservative per-core VMEM ceiling (TPU cores carry ~16 MB; leave margin
# for Pallas double-buffering of the streamed operands).
_DEF_VMEM = 12 * 1024 * 1024

# Mixed-precision schedule policies for the recurrence family.  "fp32"
# means "the plan dtype" (no down-cast; chunked schedules stay bitwise
# equal to the monolithic kernel); "bf16" stores the recurrence state and
# generated d-rows in bfloat16 while coefficients and the contraction
# accumulate in the plan dtype (see kernels.streaming).
PRECISIONS = ("fp32", "bf16")

# Measured worst-case RELATIVE error (max |bf16 - fp32| / max |fp32|,
# worse of forward/inverse) of the bf16-storage schedule per bandwidth,
# with ~4x headroom over the benchmarks/error_table.py measurements
# (B <= 128 measured in interpret mode -- B = 128 measured on d-free
# streaming-built plans via `error_table.py --paper-scale`: 2.11e-2
# forward / 1.94e-2 inverse, so the bf16 rounding error has FLATTENED
# by paper scale rather than keeping the small-B ~2.6x-per-doubling
# growth the old extrapolation assumed; B in PRECISION_BOUND_EXTRAPOLATED
# keeps that conservative extrapolation, pending hardware runs, and is
# flagged loudly by Transform.describe()).
# This table GATES the static heuristic: bf16 is only auto-selected at
# bandwidths with a recorded bound, and the error-table benchmark (and
# tests/test_streaming.py) fail if a measurement ever exceeds its gate.
PRECISION_ERROR_BOUNDS = {
    8: 1.2e-2,
    16: 1.5e-2,
    32: 3e-2,
    64: 8e-2,
    128: 9e-2,
    256: 5e-1,
    512: 1.3e0,
}

# Bandwidths whose PRECISION_ERROR_BOUNDS entry is still an extrapolation
# rather than an error_table.py measurement.  describe() warns when a bf16
# schedule leans on one of these; benchmarks/error_table.py shrinks this
# set as streaming plans make larger measurements feasible.
PRECISION_BOUND_EXTRAPOLATED = frozenset({256, 512})

# Measured max RELATIVE roundtrip error (forward(inverse(fhat)) vs fhat
# over the valid-coefficient mask, worst seed) of the FP32 fused plan per
# bandwidth, with ~4x headroom.  This is the accuracy-regression guard
# for the in-kernel f32 Wigner recurrence drift at the top of the band
# (~2.2e-3 in d by l = 127 at B = 128 -- ROADMAP's fp32 accuracy cliff):
# tests/test_streaming.py and benchmarks/error_table.py measure the
# roundtrip against these gates, so a recurrence/seed change that worsens
# the drift fails loudly instead of silently degrading f32 serving.
# B <= 64 measured on the CPU (worst of 3 seeds: 7.3e-6 / 1.9e-5 /
# 1.5e-3 / 1.3e-3); B = 128 carries the ~0.13 streaming-plan measurement
# on the CPU (max-abs over max-abs).  On a TPU v5e chip_smoke.py measures
# 0.337 at B = 128 with that metric: the chip's f32 drift is larger.
FP32_ROUNDTRIP_BOUNDS = {
    8: 3e-5,
    16: 8e-5,
    32: 6e-3,
    64: 6e-3,
    128: 4e-1,
}


def vmem_limit_bytes() -> int:
    """Per-core VMEM budget for one kernel grid step.

    $REPRO_VMEM_BYTES overrides the default (e.g. for a backend with a
    different on-chip budget, or to force-skip wide-V candidates)."""
    return int(os.environ.get("REPRO_VMEM_BYTES", _DEF_VMEM))


def estimate_vmem_bytes(*, L: int, J: int, C2: int, tk: int,
                        itemsize: int = 4, lchunk: int | None = None,
                        precision: str = "fp32",
                        panel: int | None = None,
                        limit: int | None = None) -> int:
    """Static VMEM footprint of one grid step of a fused-family tiling.

    A grid step holds seeds + the two recurrence state rows (3 * TK * J),
    the order/cos-beta vectors, the rhs tile (TK * J * C2), the
    coefficient tile and the (TK, P, J) Wigner panel: ``panel`` rows if
    given, else the depth :func:`panel_depth` derives under ``limit``.
    C2 = V*C*2 grows linearly with lane packing, which is what caps V.

    itemsize must be the PLAN dtype's (f64 plans really do hold 8-byte
    tiles; assuming fp32 under-guards them 2x).  An l-chunked streaming
    schedule (lchunk != None) shrinks the coefficient tile from
    TK * L * C2 to TK * lchunk * C2 -- the memory cliff this family
    exists to cut -- and adds the staged 2 * TK * J window block, stored
    at 2 bytes under precision="bf16".
    """
    lt = L if lchunk is None else lchunk
    extra = 0
    if lchunk is not None:                              # window block
        extra += (2 if precision == "bf16" else itemsize) * 2 * tk * J
    if panel is None:
        panel = panel_depth(L=L, J=J, C2=C2, tk=tk, itemsize=itemsize,
                            lchunk=lchunk, limit=limit)
    extra += itemsize * tk * panel * J
    return (itemsize * (3 * tk * J + 2 * tk + J + tk * J * C2
                        + tk * lt * C2) + extra)


def panel_depth(*, L: int, J: int, C2: int, tk: int, itemsize: int = 4,
                lchunk: int | None = None, limit: int | None = None) -> int:
    """Rows P of the (TK, P, J) Wigner panel the fused kernels contract
    per MXU product.  A streaming schedule's panel is its chunk (P =
    lchunk).  The monolithic kernel takes the whole degree range (P = L)
    when :func:`estimate_vmem_bytes` fits ``limit`` (default
    :func:`vmem_limit_bytes`), else the largest multiple of 8 dividing L
    that fits; when none fits, the smallest, and the schedule's VMEM
    guard judges the tile."""
    if lchunk is not None:
        return lchunk
    limit = vmem_limit_bytes() if limit is None else limit
    cands = [d for d in range(L, 0, -1) if L % d == 0
             and (d % 8 == 0 or d == L)]
    for p in cands:
        if estimate_vmem_bytes(L=L, J=J, C2=C2, tk=tk, itemsize=itemsize,
                               panel=p) <= limit:
            return p
    return cands[-1]


def estimate_live_coeff_bytes(*, tk: int, L: int, C2: int, itemsize: int = 4,
                              lchunk: int | None = None) -> int:
    """Peak VMEM-LIVE coefficient tile of one grid step: TK * L * C2
    elements for the monolithic fused kernel, TK * lchunk * C2 for a
    streaming schedule.  This is the number ``Transform.describe()``
    reports so the lchunk memory win is assertable without hardware."""
    return tk * (L if lchunk is None else lchunk) * C2 * itemsize


def estimate_hbm_bytes(*, B: int, K: int, L: int, J: int, C2: int,
                       itemsize: int = 4, lchunk: int | None = None,
                       precision: str = "fp32") -> int:
    """Estimated peak HBM residency of one transform at bandwidth B.

    Counts the (2B)^3 complex grid (the paper's second memory cliff), the
    (K, L, C2) coefficient stack and (K, J, C2) beta-grid stack, and the
    fused family's Wigner working set: seeds (K, J) plus -- for
    streaming schedules -- the (nL, 2, K, J) chunk-boundary window table
    (2-byte elements under precision="bf16").  Diagnostic, not an
    allocator: use it to see WHICH term goes over before launching."""
    grid = 2 * (2 * B) ** 3 * itemsize            # complex samples (re+im)
    stacks = (K * L * C2 + K * J * C2) * itemsize
    tables = K * J * itemsize                     # seed rows
    if lchunk is not None:
        sb = 2 if precision == "bf16" else itemsize
        tables += (L // lchunk) * 2 * K * J * sb
    return grid + stacks + tables


def estimate_host_plan_bytes(B: int, *, n_clusters: int | None = None,
                             itemsize: int = 4,
                             streaming: bool = False) -> int:
    """Estimated peak HOST RSS of plan construction at bandwidth B.

    Dense builds materialize the (K, L, J) cluster table in the plan
    dtype AND the memoized f64 fundamental table (P, L, J) it is gathered
    from -- the O(B^3) host cliff (~3.2 GB at B = 128, ~69 GB at B = 512).
    Streaming builds (build_plan(streaming=True)) never touch either:
    the host holds only the recurrence generator's O(P*J) panels (seeds +
    two state rows, f64) plus one (2, K, J) staging buffer for the
    host window source.  K = P = B(B+1)/2 clusters.
    """
    K = B * (B + 1) // 2 if n_clusters is None else n_clusters
    L, J = B, 2 * B
    if streaming:
        return 3 * K * J * 8 + 2 * K * J * itemsize
    return K * L * J * itemsize + K * L * J * 8


def static_precision(B: int, precision: str | None = None,
                     dtype=None) -> str:
    """Resolve a schedule precision.  An explicit "fp32"/"bf16" choice is
    validated and honored.  None -- the planner default -- ALWAYS resolves
    to "fp32" (the plan dtype, bitwise-safe): a default plan never trades
    accuracy behind the caller's back.  Only an explicit ``"auto"`` opts
    into the heuristic: bf16 storage at paper-scale bandwidths (B >= 128)
    whose error bound is recorded in :data:`PRECISION_ERROR_BOUNDS` --
    the error-table gate -- and only for float32 plans (``dtype``); an
    f64 plan asked for accuracy bf16 storage cannot deliver, so "auto"
    never downgrades it."""
    if precision not in (None, "auto", *PRECISIONS):
        raise ValueError(f"precision={precision!r} not in {PRECISIONS}")
    if precision in PRECISIONS:
        return precision
    if precision is None:
        return "fp32"
    fp32_plan = dtype is None or jnp.dtype(dtype) == jnp.float32
    return "bf16" if (fp32_plan and B >= 128
                      and B in PRECISION_ERROR_BOUNDS) else "fp32"


def static_lchunk(*, L: int, J: int, C2: int, tk: int, itemsize: int = 4,
                  precision: str = "fp32", limit: int | None = None,
                  monolithic_ok: bool = True) -> int | None:
    """Static l-chunk heuristic for the fused family: stay monolithic
    (None) when the full (TK, L, C2) coefficient tile fits the VMEM
    ceiling, otherwise the LARGEST divisor lchunk of L that fits (largest
    chunk = fewest window reloads + longest in-kernel recurrence runs).
    Only chunks the TPU tiling accepts are candidates: multiples of 8, or
    L itself (:func:`repro.kernels.streaming.check_lchunk`).  Raises when
    no candidate fits (shrink tk or V instead).

    ``monolithic_ok=False`` skips the monolithic fast-path and admits
    lchunk = L as a candidate: bf16 schedules have no monolithic kernel
    (make_dwt_fn forces the streaming family), so their resolution must
    return a concrete chunk."""
    limit = vmem_limit_bytes() if limit is None else limit

    def est(lc):
        return estimate_vmem_bytes(L=L, J=J, C2=C2, tk=tk,
                                   itemsize=itemsize, lchunk=lc,
                                   precision=precision, limit=limit)

    if monolithic_ok and est(None) <= limit:
        return None
    top = L + 1 if not monolithic_ok else L
    for lc in sorted((d for d in range(1, top)
                      if L % d == 0 and (d % 8 == 0 or d == L)),
                     reverse=True):
        if est(lc) <= limit:
            return lc
    raise RuntimeError(
        f"no l-chunk fits the {limit}-byte VMEM ceiling at L={L}, J={J}, "
        f"C2={C2}, tk={tk} (chunks are multiples of 8 or L; shrink tk/V "
        f"or raise $REPRO_VMEM_BYTES)")


def cache_path() -> pathlib.Path:
    return pathlib.Path(os.environ.get("REPRO_AUTOTUNE_CACHE",
                                       _DEF_CACHE)).expanduser()


def _load_cache(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def _store_cache(path: pathlib.Path, entries: dict) -> None:
    """Merge `entries` into the on-disk cache atomically.

    Re-reads before writing and uses a unique temp name so concurrent
    autotune runs (multi-host jobs, parallel benchmarks) don't clobber
    each other's freshly measured keys."""
    path.parent.mkdir(parents=True, exist_ok=True)
    merged = {**_load_cache(path), **entries}
    tmp = path.with_name(f".{path.name}.{os.getpid()}.tmp")
    tmp.write_text(json.dumps(merged, indent=1, sort_keys=True))
    tmp.replace(path)


def _divisors_leq(n: int, cands) -> list[int]:
    return [c for c in cands if c <= n and n % c == 0] or [1]


def candidate_tiles(K: int) -> list[int]:
    """Small exhaustive set of cluster tiles tk dividing K (the fused
    kernels tile only the cluster axis)."""
    tks = _divisors_leq(K, (4, 8, 16, 32))
    if tks == [1]:
        # no primary tile divides K (common for per-device cluster shards
        # of a mesh plan): fall back to the smaller divisors
        tks = _divisors_leq(K, (2, 3, 6))
    return tks


def _key(plan, kind: str, V, limit: int, n_shards: int = 1,
         overlap: str = "off", lchunk: int | None = None,
         precision: str = "fp32") -> str:
    # the VMEM ceiling is part of the key: a winner measured under a
    # tight $REPRO_VMEM_BYTES (guard skipped the wide-V candidates) must
    # not be served when the budget is back to normal, and vice versa.
    # The mesh decomposition (n_shards) is part of the key too: the
    # device-local problem is the kloc = K/n cluster shard, and OpenFFT's
    # lesson is that the winning tile is decomposition-shape-specific.
    # The /O{mode} segment keys the distributed execution mode, so a
    # schedule timed under the double-buffered overlap pipeline never
    # collides with one timed under serial per-chunk launches.  /L{n}
    # (0 = monolithic) and /P{prec} key the streaming l-chunk and the
    # storage precision: a bf16 or chunked schedule runs a different
    # kernel, so its measurements must never be served to -- or poisoned
    # by -- the monolithic fp32 schedule of the same shape.  ``kind`` is
    # "fused" for tile sweeps and "overlap" for the mode timings.
    return (f"{kind}/B{plan.B}/K{plan.n_padded}/{jnp.dtype(plan.dtype).name}"
            f"/{jax.default_backend()}/V{V}/M{limit}/S{n_shards}/O{overlap}"
            f"/L{lchunk or 0}/P{precision}")


def _local_shard_timer(plan, tk: int, n_shards: int, interpret):
    """Timing closure for the device-local fused kernel of one cluster
    shard: shard 0's seed/order block stands in for every device (the
    shard-balanced order makes the blocks work-identical, and the l0s
    schedule is the min over ALL shards by construction)."""
    from repro.core import parallel  # deferred: core.parallel imports kernels

    from . import dwt_fused as dfk

    meta = parallel.fused_shard_meta(plan, n_shards, tk)
    kloc = plan.n_padded // n_shards
    seeds = meta.seeds[:kloc]
    m, mp, cb, l0s = meta.m[:kloc], meta.mp[:kloc], meta.cb, meta.l0s

    def fn(rhs):
        return dfk.dwt_fused(seeds, m, mp, cb, rhs, l0s, B=plan.B, tk=tk,
                             interpret=interpret)

    return fn


def autotune_dwt(plan, *, Vs=(1,), reps: int = 3, refresh: bool = False,
                 cache: str | os.PathLike | None = None, interpret=None,
                 vmem_limit: int | None = None, n_shards: int = 1,
                 lchunk: int | None = None, precision: str = "fp32") -> dict:
    """Measure-and-cache the best (tk, V) for one fused-family schedule.

    Returns {"tk", "V", "per_transform_s"}.  Sweeps the
    candidate tilings for every V in Vs (V > 1 packs V transforms onto the
    kernel lane axis; scored per transform so wider packing must EARN its
    place by amortizing launch + Wigner-generation cost).

    n_shards > 1 tunes the MESH decomposition instead of the local
    problem: candidates tile the per-device cluster shard (kloc = K/n),
    and timing runs the fused device-local kernel exactly as the
    shard_map body launches it (shard-balanced seed block + replicated
    l0s schedule).  Winners are cached under a mesh-shape-specific key,
    so every mesh shape earns its own sweep (the OpenFFT lesson:
    decomposition-shape-specific tuning is where the speedup lives).

    Candidates whose static per-grid-step footprint exceeds the VMEM
    ceiling (vmem_limit, default :func:`vmem_limit_bytes`) are skipped
    BEFORE launch -- wide-V lane packing (V > 4) at large B would
    otherwise fail at compile time on hardware instead of gracefully
    losing the sweep.
    """
    if (lchunk is not None or precision == "bf16") and n_shards > 1:
        raise ValueError(
            "streaming schedules (lchunk/bf16) are not wired into the "
            "sharded executor yet; tune them at n_shards=1")
    if precision not in PRECISIONS:
        raise ValueError(f"precision={precision!r} not in {PRECISIONS}")
    path = pathlib.Path(cache) if cache is not None else cache_path()
    store = _load_cache(path)
    limit = vmem_limit_bytes() if vmem_limit is None else vmem_limit
    key = _key(plan, "fused", tuple(Vs) if len(Vs) > 1 else Vs[0], limit,
               n_shards, lchunk=lchunk, precision=precision)
    if not refresh and key in store:
        obs.inc("autotune.cache.hit")
        return store[key]
    obs.inc("autotune.cache.miss")

    K, L, J = plan.n_padded, plan.B, 2 * plan.B
    K_eff = K // n_shards       # the per-device cluster problem
    C = plan.gather_m.shape[1]
    itemsize = jnp.dtype(plan.dtype).itemsize
    rng = np.random.default_rng(0)
    best = None
    n_skipped = 0
    sweep = obs.get_recorder().span("autotune.sweep", key=key,
                                    n_shards=n_shards)
    with sweep:
        for V in Vs:
            if n_shards > 1:
                rhs = jnp.asarray(rng.normal(size=(K_eff, J, V * C * 2)),
                                  plan.dtype)
            else:
                shape = (K, J, C, 2) if V == 1 else (V, K, J, C, 2)
                rhs = jnp.asarray(rng.normal(size=shape), plan.dtype)
            for tk in candidate_tiles(K_eff):
                if estimate_vmem_bytes(L=L, J=J, C2=V * C * 2, tk=tk,
                                       itemsize=itemsize, lchunk=lchunk,
                                       precision=precision,
                                       limit=limit) > limit:
                    n_skipped += 1
                    continue
                try:
                    if n_shards > 1:
                        run = _local_shard_timer(plan, tk, n_shards,
                                                 interpret)
                    else:
                        fn = ops.make_dwt_fn(plan, tk=tk, interpret=interpret,
                                             batch=None if V == 1 else V,
                                             lchunk=lchunk,
                                             precision=precision,
                                             vmem_limit=limit)
                        run = lambda r: fn(plan, r)   # noqa: E731
                    # per-candidate timing lands in the Recorder: every
                    # sweep leaves an auditable record, not just a winner
                    t = obs.time_fn(run, rhs, reps=reps,
                                    name="autotune.candidate", key=key,
                                    V=V, tk=tk) / V
                except Exception:   # tiling rejected by the kernel -> skip
                    continue
                if best is None or t < best["per_transform_s"]:
                    best = dict(tk=tk, V=V, per_transform_s=t)
    if best is None:
        raise RuntimeError(
            f"no viable tiling for {key}"
            + (f" ({n_skipped} candidates over the {limit}-byte VMEM "
               f"ceiling; raise $REPRO_VMEM_BYTES?)" if n_skipped else ""))
    _store_cache(path, {key: best})
    return best


def static_overlap(n_shards: int) -> str:
    """Static heuristic for the distributed batch execution mode
    (``Schedule.overlap``): mesh plans (n_shards > 1) default to the
    double-buffered "pipelined" mode -- every V-chunk's all-to-all can
    hide behind a neighboring chunk's local kernel, and when it cannot
    (tiny batches, fast interconnect) the pipeline costs nothing but
    loop bookkeeping.  Single-shard plans have no collective to hide,
    so they stay "off"."""
    return "pipelined" if n_shards > 1 else "off"


def autotune_overlap(plan, mesh, axis, *, V: int = 1, tk: int | None = None,
                     n_chunks: int = 4, reps: int = 3, refresh: bool = False,
                     cache: str | os.PathLike | None = None, interpret=None,
                     vmem_limit: int | None = None) -> dict:
    """Measure-and-cache the distributed batch execution mode: time an
    n_chunks-deep lane-packed ``inverse_batch`` under overlap="off" and
    overlap="pipelined" on the REAL mesh and return the winner as
    {"overlap", "per_transform_s"}.

    Each mode's timing is cached on disk under its own ``/O{mode}`` key
    segment (see :func:`_key`) plus a ``/T{tk}`` suffix naming the
    cluster tile of the fused local kernel being timed, so overlapped
    and serial schedules never collide -- and neither do timings of
    different tile schedules (a re-swept tk re-times the modes instead
    of serving measurements of a different kernel).  The executor is
    ephemeral (fused device-local kernels built from the plan's shard
    metadata); the planner (``repro.plan(..., tune="measure")``) feeds
    the winner into ``Schedule.overlap``.  Interpret-mode CPU timing
    cannot show real collective overlap (the paired benchmark asserts
    the schedule structurally instead); on TPU hardware the measured
    winner reflects the actual interconnect/compute balance.
    """
    from repro.core import parallel  # deferred: core.parallel imports kernels

    axis = (axis,) if isinstance(axis, str) else tuple(axis)
    n_shards = int(np.prod([mesh.shape[a] for a in axis]))
    path = pathlib.Path(cache) if cache is not None else cache_path()
    store = _load_cache(path)
    limit = vmem_limit_bytes() if vmem_limit is None else vmem_limit
    K, L = plan.n_padded, plan.B
    C = plan.gather_m.shape[1]
    cdtype = (jnp.complex64 if jnp.dtype(plan.dtype) == jnp.float32
              else jnp.complex128)
    # meta resolves the default tk, which is part of the cache key: the
    # timed kernel is tile-specific, so its measurements must be too
    meta = parallel.fused_shard_meta(plan, n_shards, tk)
    rng = np.random.default_rng(0)
    packed = jnp.asarray(rng.normal(size=(n_chunks * V, K, L, C))
                         + 1j * rng.normal(size=(n_chunks * V, K, L, C)),
                         cdtype)
    results = {}
    ex = None   # ONE executor serves both modes (per-call override)
    for mode in ("off", "pipelined"):
        key = _key(plan, "overlap", V, limit, n_shards,
                   overlap=mode) + f"/T{meta.tk}"
        if not refresh and key in store:
            obs.inc("autotune.cache.hit")
            results[mode] = store[key]
            continue
        obs.inc("autotune.cache.miss")
        if ex is None:
            ex = parallel.DistExecutor(
                plan, mesh, axis, lane_width=V,
                local_dwt=parallel.make_fused_local_dwt(
                    plan, n_shards, interpret=interpret, meta=meta),
                local_idwt=parallel.make_fused_local_idwt(
                    plan, n_shards, interpret=interpret, meta=meta))
        t = obs.time_fn(lambda x: ex.inverse_batch(x, overlap=mode), packed,
                        reps=reps, name="autotune.overlap", key=key,
                        overlap=mode) / (n_chunks * V)
        entry = {"overlap": mode, "per_transform_s": t}
        _store_cache(path, {key: entry})
        results[mode] = entry
    return min(results.values(), key=lambda r: r["per_transform_s"])


def tuned_dwt_fn(plan, *, Vs=(1,), interpret=None,
                 lchunk: int | None = None, precision: str = "fp32",
                 **tune_kw):
    """make_dwt_fn with autotuned tiles (sweeps + caches on first call)."""
    cfg = autotune_dwt(plan, Vs=Vs, interpret=interpret, lchunk=lchunk,
                       precision=precision, **tune_kw)
    V = cfg["V"]
    return ops.make_dwt_fn(plan, tk=cfg["tk"], batch=None if V == 1 else V,
                           lchunk=lchunk, precision=precision,
                           interpret=interpret)


def tuned_idwt_fn(plan, *, Vs=(1,), interpret=None,
                  lchunk: int | None = None, precision: str = "fp32",
                  **tune_kw):
    """make_idwt_fn sharing the forward sweep's tiling (same data layout)."""
    cfg = autotune_dwt(plan, Vs=Vs, interpret=interpret, lchunk=lchunk,
                       precision=precision, **tune_kw)
    V = cfg["V"]
    return ops.make_idwt_fn(plan, tk=cfg["tk"], batch=None if V == 1 else V,
                            lchunk=lchunk, precision=precision,
                            interpret=interpret)
