"""Mesh-resident distributed executor for FSOFT / iFSOFT (paper Sec. 3).

:class:`DistExecutor` owns everything one (plan, mesh, axis) pairing
needs to execute sharded transforms -- the shard ``PartitionSpec``s, the
reflection/sign tables, the device-local DWT/iDWT closures, and the
jitted ``shard_map`` callables -- built ONCE when the executor is
constructed and reused by every subsequent call.  Executors are normally
owned by a :class:`repro.plan.Transform` (``plan(B, mesh=...)``); the
module-level :func:`dist_executor` memoizes standalone ones.

Pipeline (forward; inverse is the exact mirror):

  stage 1  beta-sharded:   each device FFTs its own beta-slices of the
           sample grid (j is untouched by the (alpha, gamma) FFT) and
           gathers the symmetry-cluster RHS columns for ALL clusters on
           its local j-range (paper: S(m, m'; j)).
  reshard  ONE all-to-all swaps (cluster, j) ownership: afterwards each
           device owns the full j-range of ITS kappa-shard of clusters.
           This is the only communication in the transform.
  stage 2  cluster-sharded: beta-reflections become local j-reversals,
           then the clustered DWT contraction runs entirely device-local
           (the paper's 'exclusive memory range' property).

Batches ride the kernel's lane axis INSIDE the shard_map:
``forward_lanes`` / ``inverse_lanes`` take a (V, ...) transform stack,
fold the V lanes into the contraction axis (C2 = V*C*2), and issue ONE
all-to-all and one local-kernel launch for the whole stack -- V
transforms cost one collective instead of V (``forward_batch`` /
``inverse_batch`` chunk arbitrary request counts onto that path).

Communication/compute overlap (``overlap="pipelined"``): the batch
executors can run their ceil(n/V) V-chunks through a double-buffered
pipeline inside ONE ``shard_map`` call instead of a Python loop of
serial launches.  A ``jax.lax.fori_loop`` carries a two-slot buffer:
step *i* runs chunk *i*'s device-local DWT/iDWT kernel on the slot the
previous step filled while chunk *i+1*'s all-to-all is staged into the
other slot.  The collective and the kernel in one step touch different
slots and carry no data dependence, so XLA's latency-hiding scheduler
is free to keep the interconnect and the MXU busy simultaneously --
the OpenFFT/P3DFFT communication-overlap lever.  :func:`pipeline_steps`
/ :func:`pipeline_slots` describe the static schedule (prologue,
steady-state, epilogue) for tests and benchmarks; ``overlap="off"``
keeps the serial per-chunk launches (the numerical results are
identical -- the pipeline reorders work, not arithmetic).  The mode is
normally resolved by the planner (``Schedule.overlap``, see
:mod:`repro.plan.transform` and :mod:`repro.kernels.autotune`) and can
be overridden per call: ``t.executor().inverse_batch(x, overlap="off")``.

Coefficients live in the *packed* layout out[k, l, c] (cluster-sharded,
member slot c), which the inverse consumes directly -- a distributed
roundtrip therefore needs exactly two all-to-alls and no host gather.
`packed_to_dense` / `dense_to_packed` convert at the edges when needed.

The Wigner table d[k, l, j] is sharded over clusters, so the B = 512 table
(~0.4 TB in f64) that forced the paper onto a 128 GB RAM node drops to
~1.6 GB per device on a 16x16 pod -- and the fused local kernels drop the
table entirely (recurrence seeds only).

Migration note: :func:`distributed_forward` / :func:`distributed_inverse`
are kept as thin shims over a memoized executor.  They rebuilt specs and
closures per call before; new code should hold a
``repro.plan(B, mesh=...)`` Transform (or a :func:`dist_executor`) and
call its executors instead::

    t = repro.plan(B, mesh=mesh, axis=("data",))
    fhat  = t.forward(f)              # sharded single transform
    grids = t.inverse_batch(fhats)    # lane-packed sharded batch
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs

from .compat import shard_map, shard_map_norep

from .batched import SoftPlan, fft_analysis, fft_synthesis, place_coeffs

__all__ = [
    "DistExecutor", "dist_executor", "check_mesh_compat",
    "distributed_forward", "distributed_inverse",
    "LocalDWT", "ShardMeta", "fused_shard_meta",
    "make_fused_local_dwt", "make_fused_local_idwt", "packed_to_dense",
    "dense_to_packed", "packed_to_dense_batch", "dense_to_packed_batch",
    "OVERLAP_MODES", "pipeline_steps", "pipeline_slots",
]

# batch-executor execution modes: "off" launches the V-chunks serially
# (one jitted shard_map call per chunk), "pipelined" runs them through
# the double-buffered fori_loop pipeline (one call for the whole batch,
# chunk i+1's all-to-all in flight while chunk i's local kernel runs)
OVERLAP_MODES = ("off", "pipelined")


def check_overlap_mode(overlap: str) -> str:
    if overlap not in OVERLAP_MODES:
        raise ValueError(f"overlap must be one of {OVERLAP_MODES}, "
                         f"got {overlap!r}")
    return overlap


def pipeline_steps(n_chunks: int) -> list[tuple]:
    """Static step schedule of the double-buffered pipeline over
    ``n_chunks`` V-chunks, as executed by the pipelined shard_map bodies.

    Each step is a tuple of ("collective", chunk) / ("compute", chunk)
    halves that execute CONCURRENTLY (no data dependence between them):

      step 0                (("collective", 0),)              prologue
      step 1..n_chunks-1    (("collective", i), ("compute", i-1))
      step n_chunks         (("compute", n_chunks-1),)        epilogue

    Every interior step therefore keeps one chunk's all-to-all in flight
    while the previous chunk's device-local kernel runs -- the schedule
    the structural overlap checks (benchmarks/distributed.py,
    tests/test_parallel.py) assert on.
    """
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    steps: list[tuple] = [(("collective", 0),)]
    steps += [(("collective", i + 1), ("compute", i))
              for i in range(n_chunks - 1)]
    steps.append((("compute", n_chunks - 1),))
    return steps


def pipeline_slots(n_chunks: int) -> list[tuple]:
    """Two-slot buffer index rotation behind :func:`pipeline_steps`:
    per step, (read_slot, write_slot) of the fori_loop-carried buffer
    (None for the halves a prologue/epilogue step does not have).

    Chunk i lives in slot i % 2; a step reads chunk i-1 from slot
    (i-1) % 2 while the collective writes chunk i into slot i % 2 --
    always the OTHER slot, so the staged all-to-all never clobbers the
    operand of the kernel launch it overlaps with.
    """
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    slots: list[tuple] = [(None, 0)]
    slots += [((i % 2), (i + 1) % 2) for i in range(n_chunks - 1)]
    slots.append(((n_chunks - 1) % 2, None))
    return slots


def check_mesh_compat(plan: SoftPlan, n_shards: int) -> None:
    if plan.n_padded % n_shards:
        raise ValueError(
            f"cluster axis {plan.n_padded} not divisible by {n_shards} shards"
            " -- build the plan with pad_to=n_shards")
    if (2 * plan.B) % n_shards:
        raise ValueError(
            f"beta axis {2 * plan.B} not divisible by {n_shards} shards")


def _refl_sign(plan_reflected, parity):
    """(-1)^l output factor on beta-reflected member columns."""
    return jnp.where(plan_reflected[:, None, :], parity[None, :, None],
                     jnp.ones((), parity.dtype))


# ---------------------------------------------------------------------------
# pluggable device-local DWT contraction
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalDWT:
    """Device-local DWT/iDWT contraction plugged into the shard_map paths.

    operands: global arrays handed to the shard_map body before the
    rhs/lhs; cluster_sharded: per-operand flag (True -> sharded over the
    leading cluster axis, False -> replicated); fn(*local_operands, x2)
    runs on each device's shard.  Forward contract: (Kloc, J, C2) rhs ->
    (Kloc, L, C2); inverse: (Kloc, L, C2) lhs -> (Kloc, J, C2).

    The fused variants (make_fused_local_dwt/_idwt) carry recurrence seeds
    instead of plan.d, so NO Wigner-table shard enters the shard_map at all
    -- the per-device d-footprint (~1.6 GB at B = 512 on 256 devices)
    drops to the K*J seed rows.
    """

    operands: tuple
    cluster_sharded: tuple
    fn: object
    # pallas_call bodies have no replication rule on older jax; only those
    # need the shard_map replication check disabled
    needs_norep: bool = False

    def specs(self, ax0):
        return tuple(ax0 if s else P() for s in self.cluster_sharded)

    def shard_map(self):
        return shard_map_norep if self.needs_norep else shard_map


def _normalize_local_dwt(plan, local_dwt, einsum_spec):
    if isinstance(local_dwt, LocalDWT):
        return local_dwt
    if local_dwt is None:
        def local_dwt(d, x2):  # noqa: F811 -- plain dense contraction
            return jnp.einsum(einsum_spec, d, x2,
                              preferred_element_type=d.dtype)
    # legacy contract: bare fn(d_shard, x2)
    return LocalDWT((plan.require_dense("the legacy local_dwt contract"),),
                    (True,), local_dwt)


@dataclasses.dataclass(frozen=True, eq=False)
class ShardMeta:
    """Shard metadata of one (plan, n_shards) pairing, computed ONCE and
    shared by the forward and inverse distributed paths: recurrence
    seeds/orders (replacing the d-table shard) and the per-local-tile l0
    schedule valid for every shard simultaneously."""

    n_shards: int
    tk: int
    seeds: jnp.ndarray      # (Kp, J)
    m: jnp.ndarray          # (Kp,)
    mp: jnp.ndarray         # (Kp,)
    cb: jnp.ndarray         # (J,)   cos(beta), replicated
    l0s: np.ndarray         # (kloc // tk,) int32, replicated


@functools.lru_cache(maxsize=16)
def fused_shard_meta(plan: SoftPlan, n_shards: int,
                     tk: int | None = None) -> ShardMeta:
    """Seeds/orders plus per-local-tile l0s valid for EVERY shard (the
    min over shards at each local offset: core.batched.shard_lstart).

    Memoized by (plan, n_shards, tk) identity -- plans themselves are
    memoized by build_plan, so a planner (repro.plan) and both transform
    directions read ONE metadata build instead of recomputing per call."""
    from repro.kernels import ops as kops  # deferred: kernels import core

    from .batched import shard_lstart

    kloc = plan.n_padded // n_shards
    if tk is None:  # largest cluster-tile <= 8 dividing the local count
        tk = max(t for t in range(1, min(8, kloc) + 1) if kloc % t == 0)
    if kloc % tk:
        raise ValueError(f"local cluster count {kloc} not divisible by "
                         f"tk={tk}")
    seeds, m, mp, cb = kops.onthefly_inputs(plan)
    per_shard = shard_lstart(plan, n_shards)
    l0s = per_shard.reshape(n_shards, kloc // tk, tk).min(axis=(0, 2))
    return ShardMeta(n_shards=n_shards, tk=tk, seeds=seeds, m=m, mp=mp,
                     cb=cb, l0s=np.asarray(l0s, np.int32))


def make_fused_local_dwt(plan: SoftPlan, n_shards: int, *, tk=None,
                         interpret=None, meta: ShardMeta | None = None):
    """LocalDWT running the fused ragged+on-the-fly kernel per device: no
    d-table shard, zero-triangle skipped via the replicated l0s schedule.
    Build the plan with order=shard_balanced_order(...) so every shard's
    local block is extent-sorted (correct for any order; sorted orders
    maximize the skipped rows).  `meta` accepts a precomputed
    :func:`fused_shard_meta` (e.g. from a repro.plan Transform)."""
    from repro.kernels import dwt_fused as dfk

    meta = fused_shard_meta(plan, n_shards, tk) if meta is None else meta
    l0s, mtk = meta.l0s, meta.tk

    def fn(seeds_loc, m_loc, mp_loc, cb_rep, rhs2):
        return dfk.dwt_fused(seeds_loc, m_loc, mp_loc, cb_rep, rhs2, l0s,
                             B=plan.B, tk=mtk, interpret=interpret)

    return LocalDWT((meta.seeds, meta.m, meta.mp, meta.cb),
                    (True, True, True, False), fn, needs_norep=True)


def make_fused_local_idwt(plan: SoftPlan, n_shards: int, *, tk=None,
                          interpret=None, meta: ShardMeta | None = None):
    """Inverse-path twin of make_fused_local_dwt (no d-table shard)."""
    from repro.kernels import dwt_fused as dfk

    meta = fused_shard_meta(plan, n_shards, tk) if meta is None else meta
    l0s, mtk = meta.l0s, meta.tk

    def fn(seeds_loc, m_loc, mp_loc, cb_rep, lhs2):
        return dfk.idwt_fused(seeds_loc, m_loc, mp_loc, cb_rep, lhs2, l0s,
                              B=plan.B, tk=mtk, interpret=interpret)

    return LocalDWT((meta.seeds, meta.m, meta.mp, meta.cb),
                    (True, True, True, False), fn, needs_norep=True)


# ---------------------------------------------------------------------------
# the mesh-resident executor
# ---------------------------------------------------------------------------

class DistExecutor:
    """Sharded FSOFT/iFSOFT executors of one (plan, mesh, axis) pairing.

    Construction normalizes the shard axes, validates mesh compatibility,
    and binds the device-local DWT/iDWT closures (`local_dwt` /
    `local_idwt` follow the :func:`distributed_forward` contract: None ->
    plain einsum over the sharded d-table, a bare fn(d_shard, x2), or a
    :class:`LocalDWT` such as :func:`make_fused_local_dwt`).  The jitted
    ``shard_map`` callables are built lazily ONCE per direction and
    reused by every call -- per-call spec/closure rebuilding (the old
    ``distributed_*`` behavior) is gone.

    All executors speak the packed coefficient layout (K, L, C); batch
    entry points carry a leading lane axis:

      forward(f) / inverse(packed)        single transform
      forward_lanes / inverse_lanes       exactly-V stack, ONE all-to-all
                                          and one local launch for all V
      forward_batch / inverse_batch       any count, chunked to lane_width

    Lane packing folds the V transforms into the local kernel's
    contraction lane axis (C2 = V*C*2), so the fused kernel generates
    each on-the-fly Wigner row once per V transforms and the collective
    payload per transform is unchanged while the collective COUNT drops
    V-fold.

    ``overlap`` sets the default batch execution mode (:data:`
    OVERLAP_MODES`): "off" launches the ceil(n/V) chunks serially;
    "pipelined" folds them into one shard_map call whose fori_loop
    carries a two-slot buffer, so chunk i's local kernel overlaps chunk
    i+1's all-to-all (see :func:`pipeline_steps`).  The batch executors
    accept a per-call ``overlap=`` override; ``forward_lanes`` /
    ``inverse_lanes`` are the single-chunk primitive the pipeline is
    built from and have no mode of their own.
    """

    def __init__(self, plan: SoftPlan, mesh, axis=("data", "model"), *,
                 lane_width: int = 1, local_dwt=None, local_idwt=None,
                 overlap: str = "off"):
        self.plan = plan
        self.mesh = mesh
        self.axis = (axis,) if isinstance(axis, str) else tuple(axis)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axis]))
        check_mesh_compat(plan, self.n_shards)
        if lane_width < 1:
            raise ValueError(f"lane_width must be >= 1, got {lane_width}")
        self.lane_width = int(lane_width)
        self.overlap = check_overlap_mode(overlap)
        self._ld = _normalize_local_dwt(plan, local_dwt, "klj,kjc->klc")
        self._lid = _normalize_local_dwt(plan, local_idwt, "klj,klc->kjc")
        self._calls: dict = {}

    @property
    def _shard(self):
        """The flattened shard axis name(s) for PartitionSpecs."""
        return self.axis if len(self.axis) > 1 else self.axis[0]

    # -- sharded callables (built once, jitted, cached) -----------------
    #
    # Both directions decompose into three stages shared by the serial
    # (one V-chunk per call) and pipelined (fori_loop over chunks,
    # two-slot buffer) bodies:
    #
    #   forward:  stage1 beta-local FFT+gather -> all-to-all -> stage2
    #             local DWT kernel + sign/scale postprocess
    #   inverse:  stage1 signs + local iDWT kernel + reflection flip ->
    #             all-to-all -> stage2 bin scatter + FFT synthesis
    #
    # The collective always sits between a compute stage it does NOT
    # depend on for the NEIGHBORING chunk -- that independence is what
    # the pipelined bodies exploit.

    def _forward_stages(self, refl, sign, gm, gmp, w, scale, parity,
                        dwt_ops):
        axis, n, ld = self.axis, self.n_shards, self._ld
        C = self.plan.gather_m.shape[1]

        # jax.named_scope labels are trace-time metadata only (no runtime
        # cost, no numeric change): they make the all-to-all vs local-
        # kernel split visible on device timelines (XLA profiles), lining
        # up with the host-side obs spans around each dispatch.
        def stage1(f_loc):
            # f_loc: (V, 2B, jloc, 2B) lane stack of beta shards;
            # sign/gm/gmp replicated (pre-reshard, full K), w beta-local
            with jax.named_scope("obs.fft_gather"):
                S = jax.vmap(fft_analysis)(f_loc)     # (V, 2B, jloc, 2B)

                def gather(s):
                    Sm = s[gm, :, gmp]                # (K, C, jloc)
                    r = Sm * (sign[..., None] * w[None, None, :])
                    r = jnp.stack([r.real, r.imag], -1)  # (K, C, jloc, 2)
                    return jnp.swapaxes(r, 1, 2)      # (K, jloc, C, 2)

                rhs = jax.vmap(gather)(S)             # (V, K, jloc, C, 2)
                V, K, jloc = rhs.shape[:3]
                rhs = jnp.moveaxis(rhs, 0, 2)         # (K, jloc, V, C, 2)
                return rhs.reshape(K, jloc, V * C * 2)

        def reshard(rhs):
            # ONE all-to-all reshards all V lanes together:
            # (K, jloc, VC2) beta-sharded -> (K/n, jloc*n, VC2)
            with jax.named_scope("obs.all_to_all"):
                return jax.lax.all_to_all(rhs, axis, split_axis=0,
                                          concat_axis=1, tiled=True)

        def stage2(rhs):
            # refl/scale applied post-reshard on the cluster shard
            with jax.named_scope("obs.local_kernel"):
                Kn, jn = rhs.shape[0], rhs.shape[1]
                V = rhs.shape[2] // (C * 2)
                rhs = rhs.reshape(Kn, jn, V, C, 2)
                rhs = jnp.where(refl[:, None, None, :, None],
                                rhs[:, ::-1], rhs)
                out = ld.fn(*dwt_ops, rhs.reshape(Kn, jn, V * C * 2))
                out = out.reshape(*out.shape[:2], V, C, 2)
                outc = out[..., 0] + 1j * out[..., 1]  # (Kloc, L, V, C)
                outc = outc * (_refl_sign(refl, parity)[:, :, None, :]
                               * scale[None, :, None, None])
                return jnp.moveaxis(outc, 2, 0)       # (V, Kloc, L, C)

        return stage1, reshard, stage2

    def _inverse_stages(self, refl, sign_sh, sign, gm, gmp, parity,
                        idwt_ops):
        axis, ld = self.axis, self._lid
        B = self.plan.B
        C = self.plan.gather_m.shape[1]

        def stage1(packed_loc):
            # packed_loc: (V, Kloc, L, C) lane stack of cluster shards;
            # sign_sh cluster-sharded (scales the local lhs)
            with jax.named_scope("obs.local_kernel"):
                lhs = packed_loc * (_refl_sign(refl, parity)[None]
                                    * sign_sh[None, :, None, :])
                lhs = jnp.stack([lhs.real, lhs.imag], -1)  # (V,Kloc,L,C,2)
                V, Kloc, L = lhs.shape[:3]
                lhs = jnp.moveaxis(lhs, 0, 2)          # (Kloc, L, V, C, 2)
                g = ld.fn(*idwt_ops, lhs.reshape(Kloc, L, V * C * 2))
                J = g.shape[1]
                g = g.reshape(Kloc, J, V, C, 2)
                g = jnp.where(refl[:, None, None, :, None], g[:, ::-1], g)
                return g.reshape(Kloc, J, V * C * 2)

        def reshard(g):
            # ONE all-to-all reshards all V lanes together:
            # (Kloc, J, VC2) cluster-sharded -> (K, jloc, VC2)
            with jax.named_scope("obs.all_to_all"):
                return jax.lax.all_to_all(g, axis, split_axis=1,
                                          concat_axis=0, tiled=True)

        def stage2(g):
            # sign replicated: masks the global bin scatter post-reshard
            with jax.named_scope("obs.scatter_fft"):
                K, jloc = g.shape[0], g.shape[1]
                V = g.shape[2] // (C * 2)
                g = g.reshape(K, jloc, V, C, 2)
                gc = g[..., 0] + 1j * g[..., 1]        # (K, jloc, V, C)
                # scatter member columns into FFT bins (unused -> bin 2B)
                gmask = jnp.where(sign != 0, gm, 2 * B).reshape(-1)
                gmpask = jnp.where(sign != 0, gmp, 2 * B).reshape(-1)

                def scatter(gl):                       # (K, jloc, C)
                    buf = jnp.zeros((2 * B + 1, jloc, 2 * B + 1),
                                    dtype=gl.dtype)
                    vals = jnp.swapaxes(gl, 1, 2).reshape(-1, jloc)
                    buf = buf.at[gmask, :, gmpask].set(vals, mode="drop")
                    return fft_synthesis(buf[: 2 * B, :, : 2 * B])

                return jax.vmap(scatter, in_axes=2)(gc)  # (V,2B,jloc,2B)

        return stage1, reshard, stage2

    @property
    def _cdtype(self):
        return (jnp.complex64 if jnp.dtype(self.plan.dtype) == jnp.float32
                else jnp.complex128)

    def _forward_call(self):
        fn = self._calls.get("fwd")
        if fn is not None:
            return fn
        ld, ax0 = self._ld, P(self._shard)

        def body(refl, sign, gm, gmp, w, scale, parity, f_loc, *dwt_ops):
            stage1, reshard, stage2 = self._forward_stages(
                refl, sign, gm, gmp, w, scale, parity, dwt_ops)
            return stage2(reshard(stage1(f_loc)))

        sharded = ld.shard_map()(
            body, mesh=self.mesh,
            in_specs=(ax0, P(), P(), P(), ax0, P(), P(),
                      P(None, None, self._shard, None)) + ld.specs(ax0),
            out_specs=P(None, self._shard),
        )
        fn = jax.jit(sharded)
        self._calls["fwd"] = fn
        return fn

    def _inverse_call(self):
        fn = self._calls.get("inv")
        if fn is not None:
            return fn
        ld, ax0 = self._lid, P(self._shard)

        def body(refl, sign_sh, sign, gm, gmp, parity, packed_loc,
                 *idwt_ops):
            stage1, reshard, stage2 = self._inverse_stages(
                refl, sign_sh, sign, gm, gmp, parity, idwt_ops)
            return stage2(reshard(stage1(packed_loc)))

        sharded = ld.shard_map()(
            body, mesh=self.mesh,
            in_specs=(ax0, ax0, P(), P(), P(), P(),
                      P(None, self._shard)) + ld.specs(ax0),
            out_specs=P(None, None, self._shard, None),
        )
        fn = jax.jit(sharded)
        self._calls["inv"] = fn
        return fn

    # -- the double-buffered pipelined callables ------------------------

    def _forward_pipe_call(self):
        """Whole-batch forward: (n_chunks, V, 2B, 2B, 2B) in ONE
        shard_map call.  The fori_loop body reads chunk i from its
        buffer slot and launches the local DWT kernel on it while chunk
        i+1's all-to-all is staged into the OTHER slot -- the two halves
        share no data, so the scheduler can overlap them (see
        :func:`pipeline_steps` / :func:`pipeline_slots`)."""
        fn = self._calls.get("fwd_pipe")
        if fn is not None:
            return fn
        ld, ax0 = self._ld, P(self._shard)
        L = self.plan.B
        C = self.plan.gather_m.shape[1]
        cdtype = self._cdtype

        def body(refl, sign, gm, gmp, w, scale, parity, f_all, *dwt_ops):
            stage1, reshard, stage2 = self._forward_stages(
                refl, sign, gm, gmp, w, scale, parity, dwt_ops)
            nc, V = f_all.shape[0], f_all.shape[1]
            # prologue: chunk 0 through stage 1 + its collective.  Stage
            # 1 runs per chunk INSIDE the loop (not vmapped up front) so
            # only two resharded chunks are ever live -- the pipeline's
            # footprint stays at the two-slot buffer, not the batch.
            first = reshard(stage1(f_all[0]))
            buf = jnp.zeros((2,) + first.shape, first.dtype).at[0].set(first)
            # the carry varies over the shard axes, as the body's updates do
            out = jax.lax.pcast(jnp.zeros((nc, V, first.shape[0], L, C),
                                          cdtype), self.axis, to="varying")

            def step(i, carry):
                buf, out = carry
                # read chunk i from the CARRIED buffer (not the updated
                # one): the kernel launch below must not depend on the
                # collective being staged this step
                cur = jax.lax.dynamic_index_in_dim(buf, i % 2, 0,
                                                   keepdims=False)
                nxt = reshard(stage1(jax.lax.dynamic_index_in_dim(
                    f_all, i + 1, 0, keepdims=False)))
                buf = jax.lax.dynamic_update_index_in_dim(
                    buf, nxt, (i + 1) % 2, 0)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, stage2(cur), i, 0)
                return buf, out

            buf, out = jax.lax.fori_loop(0, nc - 1, step, (buf, out))
            last = stage2(jax.lax.dynamic_index_in_dim(
                buf, (nc - 1) % 2, 0, keepdims=False))
            return jax.lax.dynamic_update_index_in_dim(out, last, nc - 1, 0)

        sharded = ld.shard_map()(
            body, mesh=self.mesh,
            in_specs=(ax0, P(), P(), P(), ax0, P(), P(),
                      P(None, None, None, self._shard, None))
            + ld.specs(ax0),
            out_specs=P(None, None, self._shard),
        )
        fn = jax.jit(sharded)
        self._calls["fwd_pipe"] = fn
        return fn

    def _inverse_pipe_call(self):
        """Whole-batch inverse: (n_chunks, V, Kloc*n, L, C) in ONE
        shard_map call.  Mirror pipeline of :meth:`_forward_pipe_call`:
        here stage 1 IS the local iDWT kernel, so the loop launches
        chunk i+1's kernel while chunk i's all-to-all is in flight."""
        fn = self._calls.get("inv_pipe")
        if fn is not None:
            return fn
        n, ld, ax0 = self.n_shards, self._lid, P(self._shard)
        B = self.plan.B
        cdtype = self._cdtype

        def body(refl, sign_sh, sign, gm, gmp, parity, packed_all,
                 *idwt_ops):
            stage1, reshard, stage2 = self._inverse_stages(
                refl, sign_sh, sign, gm, gmp, parity, idwt_ops)
            nc, V = packed_all.shape[0], packed_all.shape[1]
            jloc = 2 * B // n
            first = stage1(packed_all[0])         # prologue: chunk 0 kernel
            buf = jnp.zeros((2,) + first.shape, first.dtype).at[0].set(first)
            out = jax.lax.pcast(jnp.zeros((nc, V, 2 * B, jloc, 2 * B),
                                          cdtype), self.axis, to="varying")

            def step(i, carry):
                buf, out = carry
                cur = jax.lax.dynamic_index_in_dim(buf, i % 2, 0,
                                                   keepdims=False)
                resharded = reshard(cur)          # chunk i's collective ...
                nxt = stage1(jax.lax.dynamic_index_in_dim(
                    packed_all, i + 1, 0, keepdims=False))
                # ... overlaps chunk i+1's local kernel (independent slot)
                buf = jax.lax.dynamic_update_index_in_dim(
                    buf, nxt, (i + 1) % 2, 0)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, stage2(resharded), i, 0)
                return buf, out

            buf, out = jax.lax.fori_loop(0, nc - 1, step, (buf, out))
            last = stage2(reshard(jax.lax.dynamic_index_in_dim(
                buf, (nc - 1) % 2, 0, keepdims=False)))
            return jax.lax.dynamic_update_index_in_dim(out, last, nc - 1, 0)

        sharded = ld.shard_map()(
            body, mesh=self.mesh,
            in_specs=(ax0, ax0, P(), P(), P(), P(),
                      P(None, None, self._shard)) + ld.specs(ax0),
            out_specs=P(None, None, None, self._shard, None),
        )
        fn = jax.jit(sharded)
        self._calls["inv_pipe"] = fn
        return fn

    # -- executors -------------------------------------------------------

    def forward_lanes(self, fs):
        """Exactly-V lane stack (V, 2B, 2B, 2B) -> packed (V, K, L, C):
        one all-to-all and one local DWT launch for the whole stack."""
        p = self.plan
        return self._forward_call()(
            p.reflected, p.sign, p.gather_m, p.gather_mp, p.w, p.scale,
            p.parity, jnp.asarray(fs), *self._ld.operands)

    def inverse_lanes(self, packed):
        """Exactly-V packed stack (V, K, L, C) -> samples (V, 2B, 2B, 2B)."""
        p = self.plan
        return self._inverse_call()(
            p.reflected, p.sign, p.sign, p.gather_m, p.gather_mp, p.parity,
            jnp.asarray(packed), *self._lid.operands)

    def forward(self, f):
        """FSOFT: samples (2B, 2B, 2B) -> packed coefficients (K, L, C)."""
        return self.forward_lanes(jnp.asarray(f)[None])[0]

    def inverse(self, packed):
        """iFSOFT: packed coefficients (K, L, C) -> samples (2B, 2B, 2B)."""
        return self.inverse_lanes(jnp.asarray(packed)[None])[0]

    def forward_batch(self, fs, *, stats=None, overlap=None):
        """Any request count, chunked onto lane_width-wide sharded
        launches (final partial chunk zero-padded: one compiled shape).
        ``overlap`` overrides the executor's default mode for this call
        ("off": serial per-chunk launches; "pipelined": one
        double-buffered shard_map call for the whole batch)."""
        return self._batch(fs, self.forward_lanes, stats, overlap)

    def inverse_batch(self, packed, *, stats=None, overlap=None):
        return self._batch(packed, self.inverse_lanes, stats, overlap)

    def _batch(self, xs, lanes_fn, stats, overlap=None):
        from repro.kernels import ops as kops   # deferred: kernels import core
        mode = check_overlap_mode(self.overlap if overlap is None
                                  else overlap)
        xs = jnp.asarray(xs)
        fwd = getattr(lanes_fn, "__func__", None) is \
            DistExecutor.forward_lanes
        if xs.shape[0] == 0:
            p = self.plan
            shape = ((p.n_padded, p.B, p.gather_m.shape[1]) if fwd
                     else (2 * p.B,) * 3)
            return jnp.zeros((0,) + shape, self._cdtype)
        if mode == "pipelined":
            return self._batch_pipelined(xs, fwd, stats)
        V = self.lane_width
        direction = "forward" if fwd else "inverse"
        outs = []
        for n0 in range(0, xs.shape[0], V):
            chunk, n = kops.pad_lanes(xs[n0: n0 + V], V)
            # host-side dispatch span per chunk (the all-to-all + local
            # kernel run inside the jitted shard_map; their device-side
            # split is labeled by named_scopes -- see _forward_stages).
            with obs.span("executor.chunk", mode="off", direction=direction,
                          chunk=n0 // V, lanes=n, n_shards=self.n_shards):
                out = lanes_fn(chunk)
            if stats is not None:
                stats["launches"] += 1
                stats["transforms"] += n
                stats["padded_lanes"] += V - n
            outs.append(out[:n])       # stay on device: no per-chunk sync
        return jnp.concatenate(outs, axis=0)

    def _batch_pipelined(self, xs, fwd, stats):
        """The whole batch as ONE double-buffered shard_map call: pad to
        n_chunks * V, reshape to (n_chunks, V, ...), pipeline.  Launch
        accounting is identical to the serial path (each chunk still
        runs one local-kernel launch and one all-to-all); only their
        SCHEDULE changes, so stats stay comparable across modes."""
        n, V = xs.shape[0], self.lane_width
        n_chunks = -(-n // V)
        pad = n_chunks * V - n
        if pad:
            xs = jnp.concatenate(
                [xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)])
        xs = xs.reshape((n_chunks, V) + xs.shape[1:])
        p = self.plan
        direction = "forward" if fwd else "inverse"
        # ONE span for the whole fori_loop pipeline (the chunks execute
        # inside a single jitted call, so per-chunk host spans would be
        # fiction); the two-slot rotation is recorded as the slot ids of
        # pipeline_slots so the trace documents the schedule that ran
        with obs.span("executor.pipeline", direction=direction,
                      n_chunks=n_chunks, lanes=n, padded=pad,
                      n_shards=self.n_shards,
                      slots=[list(s) for s in pipeline_slots(n_chunks)]):
            if fwd:
                out = self._forward_pipe_call()(
                    p.reflected, p.sign, p.gather_m, p.gather_mp, p.w,
                    p.scale, p.parity, xs, *self._ld.operands)
            else:
                out = self._inverse_pipe_call()(
                    p.reflected, p.sign, p.sign, p.gather_m, p.gather_mp,
                    p.parity, xs, *self._lid.operands)
        if stats is not None:
            stats["launches"] += n_chunks
            stats["transforms"] += n
            stats["padded_lanes"] += pad
        return out.reshape((n_chunks * V,) + out.shape[2:])[:n]


@functools.lru_cache(maxsize=8)
def dist_executor(plan: SoftPlan, mesh, axis=("data", "model")) -> DistExecutor:
    """Memoized default-contraction executor per (plan, mesh, axis) --
    what the :func:`distributed_forward` / :func:`distributed_inverse`
    shims execute on.  Plans and meshes hash by identity/value, so
    repeated shim calls reuse ONE executor (and its jitted callables)."""
    return DistExecutor(plan, mesh, axis)


def _shim_executor(plan, mesh, axis, **kw):
    """Executor for the deprecated shims: memoized for concrete plans,
    ephemeral when the caller jitted the shim itself (a traced SoftPlan
    must not be retained in the lru_cache -- leaked tracers) or swapped
    the local contraction."""
    if any(v is not None for v in kw.values()):
        return DistExecutor(plan, mesh, axis, **kw)
    if isinstance(plan.w, jax.core.Tracer):
        return DistExecutor(plan, mesh, axis)
    return dist_executor(plan, mesh, axis)


# ---------------------------------------------------------------------------
# deprecated per-call shims (kept for the pre-executor API)
# ---------------------------------------------------------------------------

def distributed_forward(plan: SoftPlan, f, mesh, axis=("data", "model"),
                        local_dwt=None):
    """FSOFT on a mesh: f (2B, 2B, 2B) beta-sharded -> packed coefficients
    (K, B, 8) cluster-sharded.

    Deprecated shim over :class:`DistExecutor`: prefer
    ``repro.plan(B, mesh=...).forward`` (or :func:`dist_executor`), which
    build the shard specs and closures once instead of per call.
    `local_dwt` swaps the device-local contraction (a bare
    fn(d_shard, rhs2) or a LocalDWT); passing one builds an ephemeral
    executor, exactly as the old per-call path did."""
    axis = (axis,) if isinstance(axis, str) else tuple(axis)
    return _shim_executor(plan, mesh, axis, local_dwt=local_dwt).forward(f)


def distributed_inverse(plan: SoftPlan, packed, mesh, axis=("data", "model"),
                        local_idwt=None):
    """iFSOFT on a mesh: packed coefficients (K, B, 8) cluster-sharded ->
    samples (2B, 2B, 2B) beta-sharded.  Deprecated shim over
    :class:`DistExecutor`; see :func:`distributed_forward`."""
    axis = (axis,) if isinstance(axis, str) else tuple(axis)
    return _shim_executor(plan, mesh, axis,
                          local_idwt=local_idwt).inverse(packed)


# ---------------------------------------------------------------------------
# packed <-> dense coefficient layout
# ---------------------------------------------------------------------------

def packed_to_dense(plan: SoftPlan, packed):
    """packed[k, l, c] -> dense fhat[l, m + B - 1, m' + B - 1]."""
    return place_coeffs(plan, jnp.asarray(packed))


def dense_to_packed(plan: SoftPlan, fhat):
    """dense fhat -> packed[k, l, c] (raw member coefficients, no signs)."""
    fpad = jnp.pad(jnp.asarray(fhat), ((0, 0), (0, 1), (0, 1)))
    lhs = fpad[:, plan.scatter_m, plan.scatter_mp]    # (L, K, C)
    return jnp.moveaxis(lhs, 0, 1)                    # (K, L, C)


def packed_to_dense_batch(plan: SoftPlan, packed):
    """(V, K, L, C) packed lane stack -> (V, B, 2B-1, 2B-1) dense."""
    return jax.vmap(partial(packed_to_dense, plan))(jnp.asarray(packed))


def dense_to_packed_batch(plan: SoftPlan, fhat):
    """(V, B, 2B-1, 2B-1) dense stack -> (V, K, L, C) packed."""
    return jax.vmap(partial(dense_to_packed, plan))(jnp.asarray(fhat))
