"""Planner/executor layer: one :class:`Transform` owns schedule, tuning,
lanes, and sharding for the whole stack.

The paper's PCAM design separates *planning* (symmetry-folded index
ranges, work-package partitioning) from *execution*; FFTW and P3DFFT
("a framework around a tuned transform") ship the same split as a
plan-then-execute API.  Before this module every layer re-derived its
own plan -- ``ops.make_dwt_fn``, ``core.batched.forward_clustered*``,
``core.parallel.distributed_*``, ``kernels.autotune`` and
``so3.CorrelationEngine`` each picked impl/tile/V/sharding and rebuilt
caches independently.  Now the decision is made ONCE:

    from repro import plan
    t = plan(B, V="auto")                  # resolve + materialize
    fhat = t.forward(f)                    # local / sharded routed here
    grids = t.inverse_batch(fhats)         # V-lane packed launches
    res = t.correlate(f_s2, g_s2)          # application executor

A ``Transform`` resolves the kernel schedule (a member of the fused
kernel family, or the pure-jnp reference) through
:mod:`repro.kernels.autotune` --
statically via the VMEM-guard estimator by default, or with the
measured on-disk-cached sweep under ``tune="measure"`` (or
``$REPRO_PLAN_TUNE=measure``) -- then materializes and owns every
cached resource: the :class:`~repro.core.batched.SoftPlan` (Wigner
table + cluster metadata), the single and V-lane-batched kernel
closures, and (for mesh plans) the shard metadata plus the
mesh-resident :class:`repro.core.parallel.DistExecutor` (shard specs,
jitted shard_map callables, lane-packed batch bodies -- one all-to-all
per V-wide chunk).  Mesh plans carry their own schedule key: tiles,
lane width, and the communication/compute ``overlap`` mode resolve
against the per-device cluster shard, statically or through the
autotuner's per-mesh measured sweep (``Schedule.overlap`` picks whether
the batch executors run their V-chunks serially or through the
executor's double-buffered pipeline -- chunk i's local kernel
overlapping chunk i+1's all-to-all).  Downstream layers
(``core.batched``, ``core.parallel``, ``repro.so3``) are engines behind
the plan; they remain importable for kernel-level work and as
deprecation shims.

Plans are memoized: ``plan(...)`` with an identical configuration
returns the SAME ``Transform`` object (see :func:`cache_stats`), so a
serving loop, a benchmark sweep, and a correlation engine at one
bandwidth all share one set of compiled resources.  Memoization rules:
the cache key is the full configuration tuple (B, dtype, impl, V,
tk, lchunk, precision, streaming, mesh identity + shard axes, tune
mode, overlap, VMEM limit, interpret, tune-cache path); meshes hash by
object value/identity, so two distinct-but-equal mesh objects may plan
twice while one mesh object always shares.  The cache holds the 16 most
recent configurations (LRU) and :func:`cache_stats` counts mesh plans
separately.  See docs/ARCHITECTURE.md for the full layer map.
"""
from __future__ import annotations

import collections
import dataclasses
import os

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core import batched, clusters as clusters_mod, parallel
from repro.core.batched import SoftPlan
from repro.kernels import autotune, ops
from repro.kernels.runtime import resolve_interpret

__all__ = ["Transform", "Schedule", "plan", "clear_cache", "cache_stats",
           "dense_table_bytes_limit", "warm_bandwidths",
           "IMPLS", "AUTO_V_CANDIDATES"]

# the executor schedules: the pure-jnp oracle and the fused kernel family
IMPLS = ("reference", "fused")
AUTO_V_CANDIDATES = (1, 2, 4, 8)

_DEF_TK = 8


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Resolved execution schedule of one Transform.

    ``source`` records how it was picked: "explicit" (caller fixed impl,
    V and tiles), "static" (VMEM-guard estimator), or "measured"
    (:func:`repro.kernels.autotune.autotune_dwt` sweep, on-disk cached).

    ``n_shards`` is the mesh key: schedules of mesh plans are resolved
    against the per-device cluster shard (kloc = K/n_shards) -- tiles
    must divide the LOCAL cluster count and the VMEM guard sees the
    local footprint -- so every mesh shape gets its own (tk, V).

    ``overlap`` is the distributed batch execution mode ("off" |
    "pipelined", :data:`repro.core.parallel.OVERLAP_MODES`): how the
    mesh batch executors schedule their ceil(n/V) V-chunks.  Resolved
    through :mod:`repro.kernels.autotune` -- the static n_shards > 1
    heuristic by default, or measured on the real mesh under
    ``tune="measure"`` (cached under the ``/O{mode}`` key segment) --
    and always "off" for plans without a mesh.

    ``lchunk`` engages the l-chunked STREAMING fused family
    (:mod:`repro.kernels.streaming`): None runs the monolithic kernel;
    an integer divisor of B streams the coefficient stack through
    (tk, C2, lchunk) VMEM tiles; a compiled plan's chunk sits on the
    lanes, so it is a multiple of 128 or B.  The static resolver engages
    it for bf16 schedules (largest fitting chunk).  ``precision`` is the storage precision of the streaming
    Wigner working set ("fp32" = the plan dtype, bitwise-safe; "bf16" =
    bf16 window table + bf16 contraction rows, gated by
    :data:`repro.kernels.autotune.PRECISION_ERROR_BOUNDS`); both are
    keyed into the autotune cache as /L{lchunk}/P{precision}.
    """

    impl: str               # executor schedule (one of IMPLS)
    V: int                  # lane width of the batch executors
    tk: int
    source: str             # "explicit" | "static" | "measured"
    vmem_bytes: int         # static per-grid-step footprint estimate
    vmem_limit: int         # budget the schedule was resolved under
    n_shards: int = 1       # mesh decomposition the schedule was tuned for
    overlap: str = "off"    # distributed batch mode ("off" | "pipelined")
    lchunk: int | None = None   # streaming l-chunk (None = monolithic)
    precision: str = "fp32"     # streaming storage precision
    per_transform_s: float | None = None   # measured (tune="measure") only


def _tune_mode(tune) -> str:
    if tune is None:
        tune = os.environ.get("REPRO_PLAN_TUNE", "static")
    if tune not in ("static", "measure"):
        raise ValueError(f"tune must be 'static' or 'measure', got {tune!r}")
    return tune


def _default_tk(K: int) -> int:
    return max(t for t in (1, 2, 4, _DEF_TK) if K % t == 0)


def _shard_tk(tk: int, K_local: int) -> int:
    """Largest cluster tile <= tk dividing the per-device cluster count."""
    return max(t for t in range(1, min(tk, K_local) + 1) if K_local % t == 0)


def _resolve_overlap(overlap, n_shards: int) -> str:
    """Explicit overlap= passthrough, else the static autotune heuristic
    (mesh plans pipeline, single-shard plans don't)."""
    if overlap is None:
        return autotune.static_overlap(n_shards)
    return parallel.check_overlap_mode(overlap)


def _static_schedule(soft_plan: SoftPlan, impl, V, tk, limit: int,
                     n_shards: int = 1, overlap=None, lchunk=None,
                     precision=None) -> Schedule:
    """Largest lane width under the VMEM guard, default tiles.

    Mesh plans (n_shards > 1) resolve against the per-device cluster
    shard: the tile must divide kloc = K/n_shards (that is the kernel
    the shard_map body launches), and the VMEM estimate therefore
    reflects the per-device grid step, not the unsharded one.  The
    distributed batch mode resolves through the static overlap heuristic
    unless the caller fixed it (``overlap="off" | "pipelined"``).

    Streaming resolution (single-shard): an explicit ``lchunk``
    is honored; with lchunk=None the resolver first tries the monolithic
    kernel at every lane width, and only when NONE fits the VMEM budget
    does it try the streaming family -- widest lane width first, each
    with its largest fitting chunk (:func:`repro.kernels.autotune.
    static_lchunk`).  A chunk is a multiple of 128 or B and its panel
    is the chunk, so in fp32 at B <= 512 none fits where no monolithic
    panel did, and the resolver raises; the branch resolves bf16
    schedules.  The
    storage precision resolves through :func:`repro.kernels.autotune.
    static_precision` (plan-dtype-aware; only an explicit
    ``precision="auto"`` opts into the error-table bf16 heuristic).
    A bf16 schedule has no monolithic kernel (make_dwt_fn forces the
    streaming family), so its lchunk is always resolved to a concrete
    chunk here -- ``Schedule.lchunk``/``vmem_bytes`` describe the kernel
    actually launched, never the monolithic one.
    """
    K, L, J = soft_plan.n_padded, soft_plan.B, 2 * soft_plan.B
    K_local = K // n_shards
    C = soft_plan.gather_m.shape[1]
    itemsize = jnp.dtype(soft_plan.dtype).itemsize
    omode = _resolve_overlap(overlap, n_shards)
    prec = autotune.static_precision(soft_plan.B, precision,
                                     dtype=soft_plan.dtype) \
        if impl == "fused" and n_shards == 1 else "fp32"
    mono_ok = prec == "fp32"    # bf16 has no monolithic kernel
    if n_shards > 1:    # tiles must divide the per-device cluster count
        tk = _shard_tk(_DEF_TK if tk is None else tk, K_local)
    elif tk is None:
        tk = _default_tk(K_local)
    if impl == "reference":     # pure jnp: no kernel, no VMEM constraint
        source = "static" if V == "auto" else "explicit"
        V = 4 if V == "auto" else V
        return Schedule(impl, V, tk, source, 0, limit, n_shards,
                        overlap=omode)

    def est(v, lc=None):
        return autotune.estimate_vmem_bytes(L=L, J=J, C2=v * C * 2, tk=tk,
                                            itemsize=itemsize, lchunk=lc,
                                            precision=prec, limit=limit)

    if V == "auto":
        fits = [v for v in AUTO_V_CANDIDATES if est(v, lchunk) <= limit] \
            if (mono_ok or lchunk is not None) else []
        if fits:
            V = max(fits)
            source = "static"
        elif lchunk is None and n_shards == 1:
            # the monolithic coefficient tile is over budget at every
            # lane width (or bf16 forces the streaming family outright):
            # engage streaming, widest lane width first, each with its
            # largest fitting chunk
            for v in reversed(AUTO_V_CANDIDATES):
                try:
                    lchunk = autotune.static_lchunk(
                        L=L, J=J, C2=v * C * 2, tk=tk, itemsize=itemsize,
                        precision=prec, limit=limit, monolithic_ok=mono_ok)
                except RuntimeError:
                    continue
                V, source = v, "static"
                break
            else:
                raise ValueError(
                    f"no schedule fits the {limit}-byte VMEM budget for "
                    f"impl={impl} at B={soft_plan.B}, even streaming at "
                    f"the smallest tiled chunk (raise $REPRO_VMEM_BYTES or "
                    f"vmem_budget)")
        else:
            raise ValueError(
                f"no lane width fits the {limit}-byte VMEM budget for "
                f"impl={impl} at B={soft_plan.B} (min estimate "
                f"{est(1, lchunk)}; raise $REPRO_VMEM_BYTES or vmem_budget)")
    else:
        source = "explicit"
        if not mono_ok and lchunk is None:
            # explicit bf16 V: resolve the chunk make_dwt_fn will run
            # (largest that fits) so the schedule records it
            lchunk = autotune.static_lchunk(
                L=L, J=J, C2=V * C * 2, tk=tk, itemsize=itemsize,
                precision=prec, limit=limit, monolithic_ok=False)
        if est(V, lchunk) > limit:
            raise ValueError(
                f"explicit schedule impl={impl} V={V} tk={tk} needs "
                f"{est(V, lchunk)} bytes of VMEM per grid step, over the "
                f"{limit} budget (raise $REPRO_VMEM_BYTES or vmem_budget)")
    return Schedule(impl, V, tk, source, est(V, lchunk), limit, n_shards,
                    overlap=omode, lchunk=lchunk, precision=prec)


def _measured_schedule(soft_plan: SoftPlan, V, limit: int, interpret,
                       reps: int, cache, n_shards: int = 1, overlap=None,
                       mesh=None, axis=None, lchunk=None,
                       precision=None) -> Schedule:
    """Resolve via the measured autotune sweep (disk-cached winners).

    Mesh plans sweep the per-device cluster shard (autotune_dwt's
    n_shards key).  When the overlap mode is not fixed by the caller,
    mesh plans also time the distributed batch under both modes
    (:func:`repro.kernels.autotune.autotune_overlap`, each cached under
    its own /O{mode} key) and take the faster.
    """
    prec = autotune.static_precision(soft_plan.B, precision,
                                     dtype=soft_plan.dtype) \
        if n_shards == 1 else "fp32"
    if prec == "bf16" and lchunk is None:
        # bf16 has no monolithic kernel: make_dwt_fn forces the streaming
        # family at lchunk=B, so sweep/key/estimate the kernel that will
        # actually launch instead of mislabeling it monolithic
        lchunk = soft_plan.B
    Vs = AUTO_V_CANDIDATES if V == "auto" else (V,)
    best = autotune.autotune_dwt(soft_plan, Vs=Vs, reps=reps,
                                 interpret=interpret, vmem_limit=limit,
                                 cache=cache, n_shards=n_shards,
                                 lchunk=lchunk, precision=prec)
    if overlap is None and n_shards > 1 and mesh is not None:
        omode = autotune.autotune_overlap(
            soft_plan, mesh, axis, V=best["V"],
            tk=_shard_tk(best["tk"], soft_plan.n_padded // n_shards),
            reps=reps, cache=cache, interpret=interpret,
            vmem_limit=limit)["overlap"]
    else:
        omode = _resolve_overlap(overlap, n_shards)
    L, J = soft_plan.B, 2 * soft_plan.B
    C = soft_plan.gather_m.shape[1]
    est = autotune.estimate_vmem_bytes(
        L=L, J=J, C2=best["V"] * C * 2, tk=best["tk"],
        itemsize=jnp.dtype(soft_plan.dtype).itemsize,
        lchunk=lchunk, precision=prec, limit=limit)
    return Schedule("fused", best["V"], best["tk"], "measured", est, limit,
                    n_shards, overlap=omode, lchunk=lchunk, precision=prec,
                    per_transform_s=best["per_transform_s"])


class Transform:
    """One planned SO(3) FFT configuration: schedule + owned resources +
    executors.

    Build via :func:`repro.plan.plan` (or just ``repro.plan(...)``) --
    the constructor is internal.  Executors:

      forward / inverse              single transform, dense coefficient
                                     layout in/out; sharded over
                                     ``mesh`` when one was planned
      forward_batch / inverse_batch  any request count, chunked onto the
                                     V-lane fused launches (partial
                                     chunks zero-padded: one compiled
                                     kernel shape)
      s2_forward / s2_inverse        spherical-harmonic stage 0
      correlate / engine()           rotational matching on this plan

    ``stats`` counts launches / packed transforms / padded lanes; the
    batch executors accept an external ``stats`` sink so per-client
    accounting (e.g. a CorrelationEngine) composes with the shared
    cached Transform.
    """

    def __init__(self, *, soft_plan: SoftPlan, schedule: Schedule,
                 mesh=None, axis=None, n_shards: int = 1, interpret=None,
                 tune: str = "static"):
        self.soft_plan = soft_plan
        self.schedule = schedule
        self.B = soft_plan.B
        self.dtype = soft_plan.dtype
        self.mesh = mesh
        self.axis = axis
        self.n_shards = n_shards
        self.interpret = interpret
        self.tune = tune
        self.reset_stats()
        self._resources: dict = {}

    # -- schedule forwarding --------------------------------------------

    @property
    def impl(self) -> str:
        return self.schedule.impl

    @property
    def V(self) -> int:
        return self.schedule.V

    @property
    def cdtype(self):
        return (jnp.complex64 if jnp.dtype(self.dtype) == jnp.float32
                else jnp.complex128)

    def reset_stats(self) -> None:
        self.stats = dict(launches=0, transforms=0, padded_lanes=0)

    def describe(self) -> dict:
        """One flat dict for logs / benchmark rows.

        Tuning provenance is reported in full: ``tune`` is the REQUESTED
        mode ("static" | "measure") and ``source`` the RESOLVED one
        ("explicit" | "static" | "measured" -- a tune="measure" request
        can fall back to "static" when the impl has no measured sweep or
        explicit tiles pinned the schedule).  ``overlap`` is the
        distributed batch execution mode the schedule resolved to
        ("off" | "pipelined"; always "off" without a mesh).  Mesh plans
        also report the shard axis names, the per-device shard counts
        (clusters and beta rows), and the resolved per-device lane
        width.

        Memory diagnostics for paper-scale B: ``lchunk`` / ``precision``
        are the resolved streaming schedule (None / "fp32" = monolithic
        bitwise path), ``est_live_coeff_bytes`` the peak VMEM-live
        coefficient tile of one grid step (drops by ~L/lchunk when
        streaming engages), and ``est_peak_hbm_bytes`` the estimated
        whole-transform HBM residency (grid + stacks + Wigner working
        set) -- read these BEFORE launching a large B to see which tier
        would blow up.  ``panel`` is the depth P of the fused kernels'
        Wigner panel (rows per MXU product; the whole degree range B when
        the budget allows, lchunk when streaming; None off the fused
        family), at the schedule's lane width V.
        ``est_host_plan_bytes`` is the host-tier twin:
        the peak RSS plan CONSTRUCTION costs (the dense O(B^3) table
        cliff, or the streaming generator's O(P*J) panels when
        ``streaming`` is True); ``est_peak_hbm_bytes`` is None off the
        fused family.  ``precision_bound_extrapolated`` flags
        -- loudly, with a UserWarning -- a bf16 schedule whose error gate
        is still an extrapolation rather than an error_table.py
        measurement."""
        s = self.schedule
        sp = self.soft_plan
        K, L, J = sp.n_padded, sp.B, 2 * sp.B
        C = sp.gather_m.shape[1]
        itemsize = jnp.dtype(self.dtype).itemsize
        extrapolated = (s.precision == "bf16"
                        and self.B in autotune.PRECISION_BOUND_EXTRAPOLATED)
        if extrapolated:
            import warnings
            warnings.warn(
                f"bf16 schedule at B={self.B} is gated by an EXTRAPOLATED "
                f"error bound ({autotune.PRECISION_ERROR_BOUNDS[self.B]:g});"
                f" run benchmarks/error_table.py at this bandwidth to "
                f"replace it with a measurement", stacklevel=2)
        out = {
            "B": self.B, "dtype": jnp.dtype(self.dtype).name,
            "impl": s.impl, "V": s.V, "tk": s.tk,
            "tune": self.tune, "source": s.source, "overlap": s.overlap,
            "lchunk": s.lchunk, "precision": s.precision,
            "precision_bound_extrapolated": extrapolated,
            "streaming": sp.streaming,
            "vmem_bytes": s.vmem_bytes,
            "vmem_limit": s.vmem_limit, "n_shards": self.n_shards,
            "panel": autotune.panel_depth(
                L=L, J=J, C2=s.V * C * 2, tk=s.tk, itemsize=itemsize,
                lchunk=s.lchunk, limit=s.vmem_limit)
            if s.impl == "fused" else None,
            "n_clusters": sp.n_clusters,
            "n_padded": sp.n_padded,
            "est_live_coeff_bytes": autotune.estimate_live_coeff_bytes(
                tk=s.tk, L=L, C2=s.V * C * 2, itemsize=itemsize,
                lchunk=s.lchunk),
            "est_peak_hbm_bytes": autotune.estimate_hbm_bytes(
                B=self.B, K=K, L=L, J=J, C2=s.V * C * 2, itemsize=itemsize,
                lchunk=s.lchunk, precision=s.precision)
            if s.impl == "fused" else None,
            "est_host_plan_bytes": autotune.estimate_host_plan_bytes(
                self.B, n_clusters=sp.n_clusters, itemsize=itemsize,
                streaming=sp.streaming),
        }
        if self.mesh is not None:
            out.update({
                "mesh_axes": list(self.axis),
                "mesh_shape": [int(self.mesh.shape[a]) for a in self.axis],
                "shard_clusters": self.soft_plan.n_padded // self.n_shards,
                "shard_beta": 2 * self.B // self.n_shards,
                "lane_width": s.V,
            })
        # observability: what the shared Recorder has seen of the plan /
        # autotune / executor layers so far (span quantiles are seconds;
        # see repro.obs and docs/ARCHITECTURE.md "Observability")
        rec = obs.get_recorder()
        out["obs"] = {
            "counters": {k: v for k, v in rec.counters().items()
                         if k.startswith(("plan.", "autotune."))},
            "spans": rec.summary(prefix=("plan.", "autotune.",
                                         "executor.")),
        }
        return out

    # -- owned resources (built once, cached on the Transform) ----------

    def _res(self, name, build):
        if name not in self._resources:
            self._resources[name] = build()
        return self._resources[name]

    @property
    def dwt_fn(self):
        """Single-transform (plan, rhs) DWT closure; None = jnp path."""
        return self._res("dwt_1", lambda: self._make(ops.make_dwt_fn,
                                                     None))

    @property
    def idwt_fn(self):
        return self._res("idwt_1", lambda: self._make(ops.make_idwt_fn,
                                                      None))

    @property
    def dwt_fn_batch(self):
        """V-lane batch DWT closure ((V, K, C*2, J) rhs, one launch)."""
        return self._res("dwt_V", lambda: self._make(
            ops.make_dwt_fn, self.schedule.V))

    @property
    def idwt_fn_batch(self):
        return self._res("idwt_V", lambda: self._make(
            ops.make_idwt_fn, self.schedule.V))

    def _make(self, maker, batch):
        if self.schedule.impl == "reference":
            return None
        s = self.schedule
        return maker(self.soft_plan, tk=s.tk, lchunk=s.lchunk,
                     precision=s.precision, vmem_limit=s.vmem_limit,
                     interpret=self.interpret, batch=batch)

    def shard_meta(self):
        """Fused-kernel shard metadata (seeds / orders / per-tile l0s),
        computed once per plan and shared by the forward and inverse
        distributed paths (and by :mod:`repro.core.parallel` itself).

        The local cluster-tile follows the resolved schedule.tk (so the
        sharded launch never exceeds the footprint the VMEM guard
        approved), shrunk to the largest divisor of the local cluster
        count when the global tile does not divide it."""
        if self.mesh is None:
            raise ValueError("shard_meta() on a plan built without a mesh")
        kloc = self.soft_plan.n_padded // self.n_shards
        tk = _shard_tk(self.schedule.tk, kloc)
        return self._res("shard_meta", lambda: parallel.fused_shard_meta(
            self.soft_plan, self.n_shards, tk))

    def _local_dwt(self):
        def build():
            if self.schedule.impl == "fused":
                return parallel.make_fused_local_dwt(
                    self.soft_plan, self.n_shards, interpret=self.interpret,
                    meta=self.shard_meta())
            return None          # reference: plain einsum in the body
        return self._res("local_dwt", build)

    def _local_idwt(self):
        def build():
            if self.schedule.impl == "fused":
                return parallel.make_fused_local_idwt(
                    self.soft_plan, self.n_shards, interpret=self.interpret,
                    meta=self.shard_meta())
            return None          # reference: plain einsum in the body
        return self._res("local_idwt", build)

    def executor(self) -> "parallel.DistExecutor":
        """The mesh-resident :class:`repro.core.parallel.DistExecutor` of
        this plan: shard specs, sign/reflection tables, local kernel
        closures, and jitted shard_map callables, built ONCE per (plan,
        mesh) and reused by every sharded executor call.  The executor
        inherits the schedule's resolved ``overlap`` mode as its batch
        default (per-call ``overlap=`` still overrides)."""
        if self.mesh is None:
            raise ValueError("executor() on a plan built without a mesh")
        return self._res("executor", lambda: parallel.DistExecutor(
            self.soft_plan, self.mesh, self.axis,
            lane_width=self.schedule.V, overlap=self.schedule.overlap,
            local_dwt=self._local_dwt(), local_idwt=self._local_idwt()))

    # -- executors: single transform ------------------------------------

    def forward(self, f, *, stats=None):
        """FSOFT: samples (2B, 2B, 2B) -> dense coefficients
        (B, 2B-1, 2B-1).  Routes to the sharded path when the plan holds
        a mesh."""
        stats = self.stats if stats is None else stats
        stats["launches"] += 1
        stats["transforms"] += 1
        return self._forward_impl(jnp.asarray(f))

    def _forward_impl(self, f):
        if self.mesh is not None:
            packed = self.executor().forward(f)
            return parallel.packed_to_dense(self.soft_plan, packed)
        return batched.forward_clustered(self.soft_plan, f,
                                         dwt_fn=self.dwt_fn)

    def inverse(self, fhat, *, stats=None):
        """iFSOFT: dense coefficients -> samples (2B, 2B, 2B)."""
        stats = self.stats if stats is None else stats
        stats["launches"] += 1
        stats["transforms"] += 1
        return self._inverse_impl(jnp.asarray(fhat))

    def _inverse_impl(self, fhat):
        if self.mesh is not None:
            packed = parallel.dense_to_packed(self.soft_plan, fhat)
            return self.executor().inverse(packed)
        return batched.inverse_clustered(self.soft_plan, fhat,
                                         idwt_fn=self.idwt_fn)

    # -- executors: V-lane batches --------------------------------------

    def forward_batch(self, fs, *, stats=None, overlap=None):
        """FSOFT of any request count: (n, 2B, 2B, 2B) -> (n, B, 2B-1,
        2B-1).  Chunks of V ride one lane-packed kernel launch; the final
        partial chunk is zero-padded so every launch reuses the single
        compiled kernel shape.  On mesh plans each chunk is ONE
        lane-packed sharded launch (one all-to-all for all V lanes) via
        the plan's :meth:`executor`; when the schedule resolved
        ``overlap="pipelined"`` the chunks run through the executor's
        double-buffered pipeline (chunk i's local kernel overlapping
        chunk i+1's collective) instead of serially; pass ``overlap=``
        to override the resolved mode for one call (mesh plans only)."""
        return self._batch(fs, batched.forward_clustered_batch,
                           lambda: self.dwt_fn_batch, "dwt_fn",
                           out_shape=(self.B, 2 * self.B - 1, 2 * self.B - 1),
                           stats=stats, overlap=overlap)

    def inverse_batch(self, fhats, *, stats=None, overlap=None):
        """iFSOFT of any request count: (n, B, 2B-1, 2B-1) -> (n, 2B,
        2B, 2B); see :meth:`forward_batch`."""
        return self._batch(fhats, batched.inverse_clustered_batch,
                           lambda: self.idwt_fn_batch, "idwt_fn",
                           out_shape=(2 * self.B,) * 3, stats=stats,
                           overlap=overlap)

    def inverse_lanes(self, fhats):
        """iFSOFT of exactly V lane-packed transforms, traceable inside a
        caller's ``jax.jit``: (V, B, 2B-1, 2B-1) -> (V, 2B, 2B, 2B).  One
        chunk of :meth:`inverse_batch`, with no padding, slicing or
        launch accounting: a bank query (``CorrelationEngine.match_bank``)
        compiles it into its per-chunk executable.  On a mesh plan it is
        the executor's sharded lane launch."""
        if self.mesh is not None:
            return self.executor().inverse_lanes(
                parallel.dense_to_packed_batch(self.soft_plan, fhats))
        return batched.inverse_clustered_batch(self.soft_plan, fhats,
                                               idwt_fn=self.idwt_fn_batch)

    def _batch(self, xs, engine, get_fn, fn_kw, out_shape, stats,
               overlap=None):
        stats = self.stats if stats is None else stats
        if overlap is not None:
            parallel.check_overlap_mode(overlap)   # typos before routing
            if overlap != "off" and self.mesh is None:
                raise ValueError(
                    f"overlap={overlap!r} needs a mesh plan; local "
                    "batches have no collective to pipeline")
        xs = jnp.asarray(xs)
        n_total = xs.shape[0]
        if n_total == 0:
            return jnp.zeros((0,) + out_shape, self.cdtype)
        if self.mesh is not None:     # lane-packed sharded launches
            ex = self.executor()
            if fn_kw == "dwt_fn":
                packed = ex.forward_batch(xs, stats=stats, overlap=overlap)
                return parallel.packed_to_dense_batch(self.soft_plan, packed)
            packed = parallel.dense_to_packed_batch(self.soft_plan, xs)
            return ex.inverse_batch(packed, stats=stats, overlap=overlap)
        V = self.schedule.V
        fn = get_fn()
        outs = []
        direction = "forward" if fn_kw == "dwt_fn" else "inverse"
        for n0 in range(0, n_total, V):
            chunk, n = ops.pad_lanes(xs[n0: n0 + V], V)
            # host-side dispatch span (launches stay async; no sync here)
            with obs.span("executor.chunk", mode="local",
                          direction=direction, chunk=n0 // V, lanes=n):
                out = engine(self.soft_plan, chunk, **{fn_kw: fn})
            stats["launches"] += 1
            stats["transforms"] += n
            stats["padded_lanes"] += V - n
            outs.append(out[:n])      # stay on device: no per-chunk sync
        return jnp.concatenate(outs, axis=0)

    # -- executors: S^2 stage and correlation ---------------------------

    def s2_forward(self, samples):
        """S^2 analysis: samples (2B, 2B) -> coefficients (B, 2B-1)."""
        from repro.so3 import s2
        return s2.s2_analysis(samples, self.B)

    def s2_inverse(self, flm):
        """S^2 synthesis: coefficients (B, 2B-1) -> samples (2B, 2B)."""
        from repro.so3 import s2
        return s2.s2_synthesis(flm)

    def engine(self):
        """The rotational-matching engine bound to this plan (cached)."""
        from repro.so3.correlate import CorrelationEngine
        return self._res("engine", lambda: CorrelationEngine(transform=self))

    def correlate(self, f, g, *, refine: bool = True):
        """Rotation maximizing <f, Lambda(R) g> for one S^2 pair."""
        return self.engine().match(f, g, refine=refine)


# ---------------------------------------------------------------------------
# the planner entry point + plan cache
# ---------------------------------------------------------------------------

_CACHE: collections.OrderedDict = collections.OrderedDict()
_CACHE_MAX = 16
_CACHE_STATS = {"hits": 0, "misses": 0, "mesh_hits": 0, "mesh_misses": 0}


def clear_cache() -> None:
    """Drop memoized Transforms (testing / benchmarking hook)."""
    _CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


def warm_bandwidths() -> dict[int, int]:
    """{B: count of memoized Transforms at that bandwidth} -- the
    plan-cache-aware scheduling hook for the serving tier.

    A continuous-batching scheduler (``repro.so3.SO3Service``) uses this
    to prefer dispatching bandwidths whose plans are already WARM (a
    cached Transform exists: SoftPlan, Wigner resources, and compiled
    kernels are all built) over cold ones that would stall a lane behind
    a plan construction + kernel compile."""
    out: dict[int, int] = {}
    for t in _CACHE.values():
        out[t.B] = out.get(t.B, 0) + 1
    return out


def cache_stats() -> dict:
    """Planner cache counters.  hits/misses count every lookup;
    mesh_hits/mesh_misses count the mesh-planned subset separately, and
    mesh_size is how many of the cached Transforms hold a mesh.
    ``soft_plan_cache`` surfaces the byte-bounded core.batched plan memo
    (bytes / bytes_limit / evictions; $REPRO_PLAN_CACHE_BYTES)."""
    return dict(_CACHE_STATS, size=len(_CACHE),
                mesh_size=sum(1 for t in _CACHE.values()
                              if t.mesh is not None),
                soft_plan_cache=batched.plan_cache_stats())


# Dense-table host-footprint threshold (bytes) above which plan() builds
# streaming-capable configurations without the dense Wigner table.
_DEF_DENSE_TABLE_BYTES = 512 * 1024 * 1024
_LAST_PEAK_RSS = 0


def dense_table_bytes_limit() -> int:
    """Auto-streaming threshold; override with $REPRO_PLAN_DENSE_TABLE_BYTES."""
    return int(os.environ.get("REPRO_PLAN_DENSE_TABLE_BYTES",
                              _DEF_DENSE_TABLE_BYTES))


def _bump_host_peak_rss() -> None:
    """Advance the monotonic ``plan.host_peak_rss`` obs counter to the
    process's current peak RSS (bytes).  Sampled after every plan build,
    so a dense table sneaking back into a streaming path shows up as a
    counter jump in ``profile_so3 --check`` traces."""
    global _LAST_PEAK_RSS
    # Prefer /proc/self/status VmHWM over getrusage: on current kernels a
    # spawned child inherits the parent's ru_maxrss high-water mark, which
    # would charge the parent's whole footprint to this counter's first
    # bump.  VmHWM is reset at exec and reflects only this process.
    peak = None
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    peak = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    if peak is None:
        try:
            import resource
            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except (ImportError, OSError):      # non-POSIX host
            return
    if peak > _LAST_PEAK_RSS:
        obs.inc("plan.host_peak_rss", peak - _LAST_PEAK_RSS)
        _LAST_PEAK_RSS = peak


def _mesh_key(mesh):
    if mesh is None:
        return None
    try:
        return hash(mesh)
    except TypeError:
        return id(mesh)


def plan(B: int, dtype=jnp.float64, *, impl: str = "fused", V="auto",
         tk: int | None = None, lchunk: int | None = None,
         precision: str | None = None, streaming: bool | None = None,
         mesh=None, axis=("data", "model"), tune: str | None = None,
         overlap: str | None = None, vmem_budget: int | None = None,
         interpret=None, tune_reps: int = 3, tune_cache=None) -> Transform:
    """Plan one SO(3) FFT configuration; returns a memoized Transform.

    impl: "fused" (the Pallas kernel family) | "reference" (pure jnp).
    V:    "auto" or an explicit lane width for the batch executors.
    tk:   the cluster tile (default: the largest of 8, 4, 2, 1 dividing
          the cluster count).
    lchunk: None (monolithic kernel; bf16 resolves its own chunk) or an
          explicit l-chunk forcing the streaming kernel (single-shard
          fused plans only): a divisor of B, and for a compiled plan a
          multiple of 128 or B itself.
    streaming: build the SoftPlan WITHOUT the dense (K, L, J) Wigner
          table (core.batched.build_plan(streaming=True)): plan
          construction never materializes any O(B^3) host array, the
          grid FFT stages run in beta slabs, and only the fused
          kernels can execute.  None -- the default -- auto-engages it
          for fused non-mesh plans whose dense-table host footprint
          would exceed $REPRO_PLAN_DENSE_TABLE_BYTES (512 MiB default:
          B <= 64 keeps the dense build bit-for-bit, paper-scale B
          streams).  Explicit
          True/False overrides; True rejects impl="reference".
    precision: None (the default: fp32 / plan-dtype storage, bitwise-
          safe -- a default plan never trades accuracy implicitly),
          "auto" (opt-in heuristic: bf16 storage for FLOAT32 plans at
          paper-scale bandwidths with a recorded error-table bound;
          f64 plans are never downgraded), or explicit "fp32" | "bf16".
          bf16 always runs the streaming kernel, so its schedule
          resolves a concrete lchunk even when lchunk=None.
    tune: "static" (default; VMEM-guard estimator picks the widest lane
          packing that fits) or "measure" (kernels.autotune measured
          sweep, winners cached on disk).  $REPRO_PLAN_TUNE overrides
          the default.
    mesh/axis: plan the sharded executors -- the cluster axis is padded
          and shard-balance-ordered, and forward/inverse route through
          core.parallel with the plan's shard metadata.
    overlap: None (resolve: mesh plans pipeline statically, or the
          measured mode comparison under tune="measure") or an explicit
          "off" | "pipelined" distributed batch execution mode.
    vmem_budget: per-grid-step ceiling in bytes (default
          kernels.autotune.vmem_limit_bytes(), i.e. $REPRO_VMEM_BYTES).

    Identical configurations return the SAME Transform object, so every
    consumer of one configuration shares one SoftPlan, one Wigner table,
    and one set of compiled kernels.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if (impl != "reference" and jnp.dtype(dtype).itemsize == 8
            and not resolve_interpret(interpret)):
        raise ValueError(
            f"dtype={jnp.dtype(dtype).name} cannot run in compiled Pallas "
            f"kernels: the TPU compiler has no 64-bit types.  Plan in "
            f"float32 for the chip; the f64 host reference "
            f"(repro.core.soft) is what results are checked against")
    if V != "auto" and (not isinstance(V, int) or V < 1):
        raise ValueError(f"V must be 'auto' or a positive int, got {V!r}")
    if precision not in (None, "auto", *autotune.PRECISIONS):
        raise ValueError(f"precision must be None, 'auto' or one of "
                         f"{autotune.PRECISIONS}, got {precision!r}")
    if lchunk is not None or precision == "bf16":
        if impl != "fused":
            raise ValueError(
                f"streaming schedules (lchunk/bf16) exist only for the "
                f"fused family, not impl={impl!r}")
        if mesh is not None:
            raise ValueError(
                "streaming schedules (lchunk/bf16) are not wired into "
                "the sharded executor yet; plan without a mesh")
        if lchunk is not None:
            from repro.kernels import streaming as streaming_kernels
            lchunk = streaming_kernels.check_lchunk(
                B, lchunk, tiled=not resolve_interpret(interpret))
    if overlap is not None:
        parallel.check_overlap_mode(overlap)       # typos before mesh advice
        if overlap != "off" and mesh is None:
            raise ValueError(
                f"overlap={overlap!r} needs a mesh plan; local batches "
                "have no collective to pipeline")
    recurrence_capable = impl == "fused" and mesh is None
    if streaming is None:
        dense_bytes = autotune.estimate_host_plan_bytes(
            B, itemsize=jnp.dtype(dtype).itemsize)
        streaming = recurrence_capable \
            and dense_bytes > dense_table_bytes_limit()
    elif streaming and not recurrence_capable:
        raise ValueError(
            f"streaming=True needs a fused plan without a mesh; got "
            f"impl={impl!r}, "
            f"mesh={'set' if mesh is not None else None}")
    mode = _tune_mode(tune)
    limit = autotune.vmem_limit_bytes() if vmem_budget is None \
        else int(vmem_budget)
    axis = (axis,) if isinstance(axis, str) else tuple(axis)
    key = (B, jnp.dtype(dtype).str, impl, V, tk, lchunk, precision,
           bool(streaming),
           _mesh_key(mesh), axis if mesh is not None else None, mode,
           overlap, limit, interpret,
           None if tune_cache is None else str(tune_cache))
    hit = _CACHE.get(key)
    if hit is not None:
        _CACHE_STATS["hits"] += 1
        obs.inc("plan.cache.hit")
        if mesh is not None:
            _CACHE_STATS["mesh_hits"] += 1
        _CACHE.move_to_end(key)
        return hit
    _CACHE_STATS["misses"] += 1
    obs.inc("plan.cache.miss")
    if mesh is not None:
        _CACHE_STATS["mesh_misses"] += 1

    with obs.span("plan.build", B=B, impl=impl, tune=mode,
                  mesh=mesh is not None, streaming=bool(streaming)):
        base_tk = tk if tk is not None else _DEF_TK
        if mesh is not None:
            n_shards = int(np.prod([mesh.shape[a] for a in axis]))
            if (2 * B) % n_shards:
                raise ValueError(
                    f"mesh with {n_shards} shards cannot split the beta "
                    f"axis: 2B = {2 * B} is not divisible by {n_shards} "
                    f"(use a mesh whose shard-axis product divides {2 * B})")
            # the planner auto-pads the cluster axis to the mesh size, so
            # check_mesh_compat can never fail at execute time on a plan
            # path.  pad_to = n_shards keeps the padding minimal
            # (< n_shards zero rows; the schedule clamps tk to the
            # per-device count instead of padding whole tk*n blocks, which
            # could idle a shard), and the shard-balanced order is dealt
            # over the PADDED count so every shard's block stays
            # extent-sorted (maximal ragged truncation).  Compiled kernels
            # need whole 8-row cluster tiles per device (the TPU tiling of
            # their (tk, J) blocks), so there each shard is padded to one.
            pad_to = n_shards if resolve_interpret(interpret) \
                else n_shards * _DEF_TK
            l_start = clusters_mod.build_cluster_table(B).rep[:, 0]
            n_padded = -(-len(l_start) // pad_to) * pad_to
            order = batched.shard_balanced_order(l_start, n_shards,
                                                 n_padded=n_padded)
            soft_plan = batched.build_plan(B, dtype=dtype, pad_to=pad_to,
                                           order=order)
            parallel.check_mesh_compat(soft_plan, n_shards)
        else:
            n_shards = 1
            soft_plan = batched.build_plan(B, dtype=dtype, pad_to=base_tk,
                                           streaming=bool(streaming))

        # mesh plans resolve (tk, V) against the per-device shard
        with obs.span("plan.schedule", B=B, impl=impl, tune=mode,
                      n_shards=n_shards):
            if mode == "measure" and impl == "fused" and tk is None:
                schedule = _measured_schedule(
                    soft_plan, V, limit, interpret, tune_reps, tune_cache,
                    n_shards, overlap, mesh, axis, lchunk, precision)
            else:
                schedule = _static_schedule(
                    soft_plan, impl, V, tk, limit, n_shards, overlap,
                    lchunk, precision)

        t = Transform(soft_plan=soft_plan, schedule=schedule, mesh=mesh,
                      axis=axis if mesh is not None else None,
                      n_shards=n_shards, interpret=interpret, tune=mode)
    _bump_host_peak_rss()
    _CACHE[key] = t
    while len(_CACHE) > _CACHE_MAX:
        _CACHE.popitem(last=False)
    return t
