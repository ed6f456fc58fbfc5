"""Jit'd wrappers binding the Pallas kernels to the framework.

  * :func:`make_dwt_fn` / :func:`make_idwt_fn` -- drop-in replacements for
    core.batched.dwt_apply / idwt_apply (plug into forward_clustered /
    inverse_clustered via the dwt_fn argument).  They bind the fused
    kernel family: the monolithic kernels of dwt_fused.py, or their
    streaming members (streaming.py) when ``lchunk`` is set or
    ``precision="bf16"``.
  * :func:`attention` -- folded causal flash attention with automatic
    interpret-mode selection (CPU validates, TPU compiles).

All wrappers run the kernels in interpret mode on CPU so the whole test
suite exercises the real kernel bodies.
"""
from __future__ import annotations

import functools
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import quadrature, wigner
from repro.core.batched import SoftPlan

from . import dwt_fused
from . import folded_attention as fa
from . import streaming
from .runtime import default_interpret

__all__ = ["default_interpret", "make_dwt_fn", "make_idwt_fn",
           "onthefly_inputs", "fused_metadata", "streaming_inputs",
           "window_source", "host_window_stack",
           "batched_rhs", "pad_lanes", "attention"]


def _split_ri(x):
    """(K, A, C, 2) -> (K, A, C*2) merging the real/imag axis into lanes."""
    return x.reshape(*x.shape[:2], -1)


def _unsplit_ri(x, c):
    return x.reshape(*x.shape[:2], c, 2)


def pack_lanes(x):
    """(V, K, A, C, 2) -> (K, A, V*C*2): V batched transforms side by side
    on the contraction lane axis, one kernel launch for the whole batch."""
    V, K, A, C, _ = x.shape
    return jnp.moveaxis(x, 0, 2).reshape(K, A, V * C * 2)


def unpack_lanes(x, V, C):
    """(K, A, V*C*2) -> (V, K, A, C, 2), inverse of pack_lanes."""
    K, A, _ = x.shape
    return jnp.moveaxis(x.reshape(K, A, V, C, 2), 2, 0)


def pad_lanes(x, V):
    """Zero-pad a partial transform stack (n, ...) with n <= V up to the
    lane width V of a batch-compiled kernel.

    Returns (padded, n).  Padding with zeros keeps every launch on ONE
    compiled kernel shape (no per-occupancy recompiles in a serving loop);
    the padded lanes produce zero outputs the caller slices off.
    """
    n = x.shape[0]
    if n > V:
        raise ValueError(f"stack of {n} transforms exceeds lane width {V}")
    if n < V:
        x = jnp.concatenate(
            [x, jnp.zeros((V - n,) + x.shape[1:], x.dtype)])
    return x, n


@functools.lru_cache(maxsize=16)
def fused_metadata(plan: SoftPlan, tk: int):
    """Host-side ragged metadata for the fused kernel: sort clusters by
    ascending l-start (padded rows last, at B-1 -- their Wigner rows are
    identically zero) and reduce each TK-tile to its scalar-prefetch l0.

    Memoized by (plan, tk) identity: a planner building forward + inverse
    + batched variants of one schedule reads one metadata build."""
    from repro.core.batched import plan_lstart

    l_start = plan_lstart(plan)
    perm = np.argsort(l_start, kind="stable").astype(np.int32)
    l0s = dwt_fused.build_tile_lstarts(l_start[perm], tk)
    return perm, l_start, l0s


def window_source() -> str:
    """Where streaming_inputs sources its HBM window stack from:
    "device" (default; streaming.build_windows, the kernel-identical jnp
    march -- bitwise-consistent with the monolithic fused kernels) or
    "host" ($REPRO_WINDOW_SOURCE=host; host_window_stack, staged
    chunk-by-chunk from the O(P*J) host generator)."""
    import os
    src = os.environ.get("REPRO_WINDOW_SOURCE", "device")
    if src not in ("device", "host"):
        raise ValueError(f"$REPRO_WINDOW_SOURCE must be 'device' or "
                         f"'host', got {src!r}")
    return src


def host_window_stack(plan: SoftPlan, tk: int, lchunk: int,
                      precision: str = "fp32"):
    """HBM window stack (nL, 2, K, J) ingested chunk-by-chunk from the
    HOST recurrence generator (core.wigner.wigner_window_iter).

    The host working set stays at the generator's O(P*J) recurrence
    panels plus ONE (2, K, J) staging buffer -- each chunk's window is
    mapped from fundamental-pair rows to the l-start-sorted padded
    cluster order (padded rows zero) and shipped to the device before
    the next chunk is marched.  Numerically equivalent to
    streaming.build_windows (host f64 march vs device march; allclose,
    not bitwise), so the default window source stays "device" where
    bitwise parity with the monolithic fused kernels matters.
    """
    perm, _, _ = fused_metadata(plan, min(tk, plan.n_padded))
    rows = np.full(plan.n_padded, -1, np.int64)
    rows[: plan.n_clusters] = plan.table.fund_row
    rows = rows[perm]
    valid = rows >= 0
    dt = jnp.bfloat16 if precision == "bf16" else plan.dtype
    stage = np.zeros((2, plan.n_padded, 2 * plan.B), np.dtype(plan.dtype))
    chunks = []
    for win in wigner.wigner_window_iter(plan.B, lchunk):
        stage[:] = 0.0
        stage[:, valid, :] = win[:, rows[valid], :]
        # snapshot the staging buffer: jnp.asarray may alias a host numpy
        # buffer zero-copy on CPU, and stage is rewritten next chunk
        chunks.append(jnp.asarray(stage.copy()).astype(dt))
    return jnp.stack(chunks)


def streaming_inputs(plan: SoftPlan, tk: int, lchunk: int, precision: str):
    """Permuted operands + chunk-boundary windows for the streaming
    kernels (kernels/streaming.py), memoized by (plan, tk, lchunk,
    precision, window_source()) identity.

    The recurrence windows are built ONCE per configuration, on the
    l-start-sorted cluster order the fused family launches in; bf16
    precision stores them (and the in-kernel state) as bfloat16.  The
    window table is the streaming schedule's only HBM-resident Wigner
    state: (nL, 2, K, J) -- lchunk/2 x smaller than the dense d-table.
    The source is the kernel-identical jnp march by default, or the host
    generator under $REPRO_WINDOW_SOURCE=host (see window_source).
    """
    return _streaming_inputs(plan, tk, lchunk, precision, window_source())


@functools.lru_cache(maxsize=16)
def _streaming_inputs(plan: SoftPlan, tk: int, lchunk: int, precision: str,
                      source: str):
    from repro import obs

    seeds, m, mp, cb = onthefly_inputs(plan)
    perm, _, l0s = fused_metadata(plan, tk)
    seeds_p, m_p, mp_p = seeds[perm], m[perm], mp[perm]
    with obs.span("plan.build.window", B=plan.B, lchunk=lchunk,
                  precision=precision, source=source):
        if source == "host":
            windows = host_window_stack(plan, tk, lchunk, precision)
        else:
            dt = seeds.dtype
            sdt = jnp.bfloat16 if precision == "bf16" else dt
            windows = streaming.build_windows(
                seeds_p, m_p.astype(dt)[:, None], mp_p.astype(dt)[:, None],
                cb[None, :], L=plan.B, lchunk=lchunk, state_dtype=sdt)
    return seeds_p, m_p, mp_p, cb, l0s, windows


def _wrap_batch(raw, batch):
    """Lift raw(p, rhs2: (K, A, C2)) to the (plan, rhs) dwt_fn contract.

    batch=None: rhs (K, A, C, 2) (the single-transform contract).
    batch=V (any int >= 1): rhs (V, K, A, C, 2); the V transforms are
    packed onto the lane axis so the kernel launches once.
    """
    if batch is None:
        def fn(p: SoftPlan, rhs):
            if rhs.ndim != 4:
                raise ValueError(f"dwt_fn built without batch expects "
                                 f"(K, A, C, 2), got {rhs.shape}; pass "
                                 f"batch=V to make_dwt_fn for a V-stack")
            return _unsplit_ri(raw(p, _split_ri(rhs)), rhs.shape[2])
        return fn

    def fn(p: SoftPlan, rhs):
        if rhs.ndim != 5 or rhs.shape[0] != batch:
            raise ValueError(f"dwt_fn built with batch={batch}, expected "
                             f"(V, K, A, C, 2), got {rhs.shape}")
        return unpack_lanes(raw(p, pack_lanes(rhs)), batch, rhs.shape[3])
    return fn


def _check_streaming_args(lchunk, precision):
    """lchunk/precision select the streaming members of the fused family;
    returns whether they do."""
    if precision not in (None, "fp32", "bf16"):
        raise ValueError(f"precision must be 'fp32' or 'bf16', "
                         f"got {precision!r}")
    return lchunk is not None or precision == "bf16"


def _make_fn(plan: SoftPlan, *, inverse, tk, lchunk, precision, vmem_limit,
             interpret, batch):
    interpret = default_interpret() if interpret is None else interpret
    tk = min(tk, plan.n_padded)
    perm, _, l0s = fused_metadata(plan, tk)
    if _check_streaming_args(lchunk, precision):
        prec = precision or "fp32"
        lchunk = streaming.check_lchunk(
            plan.B, plan.B if lchunk is None else lchunk,
            tiled=not interpret)
        seeds_p, m_p, mp_p, cb, l0s, windows = streaming_inputs(
            plan, tk, lchunk, prec)
        kernel = streaming.idwt_streaming if inverse \
            else streaming.dwt_streaming

        def launch(p: SoftPlan, x2):
            return kernel(seeds_p, m_p, mp_p, cb, x2, l0s, windows, B=p.B,
                          tk=tk, lchunk=lchunk, precision=prec,
                          interpret=interpret)
    else:
        seeds, m, mp, cb = onthefly_inputs(plan)
        seeds_p, m_p, mp_p = seeds[perm], m[perm], mp[perm]
        kernel = dwt_fused.idwt_fused if inverse else dwt_fused.dwt_fused

        def launch(p: SoftPlan, x2):
            return kernel(seeds_p, m_p, mp_p, cb, x2, l0s, B=p.B, tk=tk,
                          vmem_limit=vmem_limit, interpret=interpret)
    inv_perm = np.argsort(perm)

    def raw(p: SoftPlan, x2):
        return launch(p, x2[perm])[inv_perm]
    return _wrap_batch(raw, batch)


def make_dwt_fn(plan: SoftPlan, *, tk=8, lchunk=None, precision=None,
                vmem_limit=None, interpret=None, batch=None):
    """Build a dwt_fn(plan, rhs) for core.batched.forward_clustered on the
    fused kernel family.

    batch=V makes the fn accept a (V, K, J, C, 2) stack of RHS
    (core.batched.forward_clustered_batch) contracted in ONE kernel launch
    with V*C*2 lanes.  lchunk selects the l-chunked streaming kernel
    (kernels/streaming.py): HBM-resident coefficients staged as (tk,
    lchunk, C2) VMEM tiles, recurrence re-seeded per chunk from a two-row
    window.  precision: "fp32" (default; compute in the plan dtype) or
    "bf16" (bf16 recurrence windows / d-rows, plan-dtype accumulation;
    forces the streaming kernel, the monolithic one has no
    mixed-precision twin).  vmem_limit (default
    :func:`repro.kernels.autotune.vmem_limit_bytes`) is the budget the
    monolithic kernel derives its Wigner panel depth against.
    """
    return _make_fn(plan, inverse=False, tk=tk, lchunk=lchunk,
                    precision=precision, vmem_limit=vmem_limit,
                    interpret=interpret, batch=batch)


def make_idwt_fn(plan: SoftPlan, *, tk=8, lchunk=None, precision=None,
                 vmem_limit=None, interpret=None, batch=None):
    """Build an idwt_fn(plan, lhs) for core.batched.inverse_clustered;
    arguments as in :func:`make_dwt_fn` (with batch=V, lhs gains a
    leading V axis, packed onto lanes for one launch)."""
    return _make_fn(plan, inverse=True, tk=tk, lchunk=lchunk,
                    precision=precision, vmem_limit=vmem_limit,
                    interpret=interpret, batch=batch)


def batched_rhs(plan: SoftPlan, S):
    """Lane-packed DWT right-hand side for V simultaneous transforms.

    S: (V, 2B, J, 2B) complex FFT-analysis outputs (stage 1 of V forward
    transforms).  Returns (K, J, V*C*2) real -- the widened-C2 operand the
    fused DWT kernels contract in a single launch.
    """
    from repro.core import batched as _b

    rhs = jax.vmap(lambda s: _b._gather_rhs(plan, s))(S)  # (V, K, J, C, 2)
    return pack_lanes(rhs)


@functools.lru_cache(maxsize=16)
def onthefly_inputs(plan: SoftPlan):
    """Seeds/orders/cos(beta) for the fused-family kernels.

    Padded clusters get zero seeds -> identically zero Wigner rows.
    Memoized by plan identity (plans are memoized by build_plan), so the
    seed-table build -- one wigner_seed per cluster -- runs once per plan
    across forward/inverse/batched/sharded consumers."""
    B = plan.B
    beta = quadrature.betas(B)
    K = plan.n_padded
    seeds = np.zeros((K, 2 * B))
    m = np.zeros(K, np.int32)
    mp = np.zeros(K, np.int32)
    for kidx in range(plan.n_clusters):
        mm, mmp = plan.table.rep[kidx]
        seeds[kidx] = wigner.wigner_seed(int(mm), int(mmp), beta)
        m[kidx], mp[kidx] = mm, mmp
    dt = plan.dtype
    return (jnp.asarray(seeds, dt), jnp.asarray(m), jnp.asarray(mp),
            jnp.asarray(np.cos(beta), dt))


def attention(q, k, v, *, bq=128, bk=128, scale=None, schedule="folded",
              interpret=None):
    """Folded causal flash attention (see kernels/folded_attention.py)."""
    interpret = default_interpret() if interpret is None else interpret
    return fa.folded_causal_attention(q, k, v, bq=bq, bk=bk, scale=scale,
                                      schedule=schedule, interpret=interpret)
