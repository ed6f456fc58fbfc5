"""Clustered / batched FSOFT & iFSOFT -- the TPU-native formulation.

This module reshapes the paper's parallel design into dense array programs:
the whole DWT stage (all clusters) becomes ONE batched contraction

    forward :  out[k, l, c] = sum_j  d[k, l, j] * rhs[k, j, c]
    inverse :  g[k, j, c]   = sum_l  d[k, l, j] * lhs[k, l, c]

where k runs over symmetry clusters (paper's work packages, kappa-ordered),
c over the <= 8 cluster members, and d is the fundamental-domain Wigner
table.  Gather/sign metadata comes from :mod:`clusters`; the inverse
index tables that place members into FFT bins and dense coefficients are
built with the plan (:func:`member_sources`).

The same plan drives
  * the pure-jnp path below (runs anywhere, differentiable),
  * the shard_map-distributed path (:mod:`parallel`) -- shard over k,
  * the Pallas DWT kernel (:mod:`repro.kernels.dwt`) -- grid over k/l tiles.

Complex arithmetic is carried as a trailing real/imag axis so the heavy
contraction is a real matmul (MXU-friendly; complex einsum would promote the
real Wigner operand and double the FLOPs).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from . import clusters as clusters_mod
from . import quadrature, soft, wigner

__all__ = ["SoftPlan", "build_plan", "plan_cache_stats",
           "fft_analysis_slab", "streamed_rhs", "streamed_synthesis",
           "forward_clustered", "inverse_clustered",
           "forward_clustered_batch", "inverse_clustered_batch"]


@dataclasses.dataclass(frozen=True, eq=False)
class SoftPlan:
    """Device-ready tables for the clustered transforms.

    All arrays are jnp; shapes use K = #clusters (padded to `pad_to` if
    given), L = B, J = 2B, C = 8 member slots.

    ``d is None`` marks a STREAMING plan (build_plan(streaming=True)):
    the dense (K, L, J) Wigner table is never materialized -- on the
    host or anywhere else -- and the recurrence family (fused/onthefly
    kernels, seeded from ``table.rep``) is the only executor.  The
    dense-table consumers (reference einsum, dense/ragged kernels,
    bucketed truncation) reject streaming plans loudly.
    """

    B: int
    table: clusters_mod.ClusterTable        # host metadata (numpy)
    d: jnp.ndarray | None   # (K, L, J)  fundamental Wigner blocks, or None
    gather_m: jnp.ndarray   # (K, C) int32  FFT bins
    gather_mp: jnp.ndarray  # (K, C)
    scatter_m: jnp.ndarray  # (K, C) int32  dense-layout bins (trash = 2B-1)
    scatter_mp: jnp.ndarray # (K, C)
    sign: jnp.ndarray       # (K, C) f32    0 marks unused slots
    reflected: jnp.ndarray  # (K, C) bool
    bin_src: jnp.ndarray    # ((2B)^2,) int32    member k*C+c of FFT bin (m, m')
    coeff_src: jnp.ndarray  # ((2B-1)^2,) int32  member of dense bin (m, m')
    w: jnp.ndarray          # (J,)   quadrature weights
    scale: jnp.ndarray      # (L,)   (2l+1)/(8 pi B)
    parity: jnp.ndarray     # (L,)   (-1)^l
    n_padded: int           # K after padding
    plan_dtype: str = "<f8" # real dtype str (the d-table's when present)

    @property
    def n_clusters(self) -> int:
        return self.table.n_clusters

    @property
    def streaming(self) -> bool:
        """True when the dense Wigner table was never built (d is None)."""
        return self.d is None

    @property
    def dtype(self):
        """The plan's real dtype; valid for dense AND streaming plans
        (``plan.d.dtype`` is not -- prefer this everywhere)."""
        return self.d.dtype if self.d is not None else jnp.dtype(self.plan_dtype)

    def require_dense(self, consumer: str):
        """The dense (K, L, J) table, or a loud error on streaming plans."""
        if self.d is None:
            raise ValueError(
                f"{consumer} needs the dense (K, L, J) Wigner table, but "
                f"this B={self.B} plan was built streaming (d=None; the "
                f"table was never materialized).  Use the recurrence "
                f"family (impl='fused'/'onthefly') or rebuild with "
                f"build_plan(streaming=False)")
        return self.d


# `d` stays a pytree child when present; a streaming plan's None child
# flattens to zero leaves (None is a registered empty pytree), so jit
# tracing works unchanged for both variants.
_PLAN_LEAVES = ("d", "gather_m", "gather_mp", "scatter_m", "scatter_mp",
                "sign", "reflected", "bin_src", "coeff_src", "w", "scale",
                "parity")


def _plan_flatten(p: SoftPlan):
    return (tuple(getattr(p, n) for n in _PLAN_LEAVES),
            (p.B, p.table, p.n_padded, p.plan_dtype))


def _plan_unflatten(aux, leaves):
    B, table, n_padded, plan_dtype = aux
    return SoftPlan(B=B, table=table, n_padded=n_padded,
                    plan_dtype=plan_dtype, **dict(zip(_PLAN_LEAVES, leaves)))


jax.tree_util.register_pytree_node(SoftPlan, _plan_flatten, _plan_unflatten)


def shard_balanced_order(l_start: np.ndarray, n_shards: int,
                         n_padded: int | None = None) -> np.ndarray:
    """Cluster permutation so that contiguous 1/n-th blocks (what shard_map
    hands each device) are (a) work-balanced ACROSS shards and (b)
    extent-sorted WITHIN each shard.

    Deal the extent-sorted clusters round-robin (paper-P3's balanced static
    schedule, cf. indexing.balanced_order) and lay shard s's hand out as
    global block s: each hand is itself descending in work, so every
    local block supports bucketed l-truncation (make_bucketed_dwt_fn).

    n_padded: the cluster count AFTER build_plan's pad_to padding.  Pad
    rows are appended at the global end, i.e. they land in the tail of
    the LAST shard(s); passing n_padded sizes the hands so the shard
    boundaries of the padded layout fall on hand boundaries (pad rows
    carry l_start = B-1 / zero work, so the last hand's sort order and
    every shard's extent-sortedness survive the padding).  Without it a
    cluster count not divisible by n_shards shifts the block boundaries
    off the hands and the per-shard sorting -- and with it the ragged
    l0-truncation -- silently degrades."""
    K = len(l_start)
    work_sorted = np.argsort(l_start, kind="stable")  # ascending m = desc work
    if n_padded is None or n_padded == K:
        return np.concatenate([work_sorted[s::n_shards]
                               for s in range(n_shards)]).astype(np.int64)
    if n_padded % n_shards:
        raise ValueError(f"n_padded={n_padded} % n_shards={n_shards}")
    kloc = n_padded // n_shards
    # real-cluster capacity per hand: pad rows fill the last shards' tails
    sizes = [kloc] * n_shards
    rem = n_padded - K
    s = n_shards - 1
    while rem > 0:
        take = min(kloc, rem)
        sizes[s] -= take
        rem -= take
        s -= 1
    hands: list[list[int]] = [[] for _ in range(n_shards)]
    idx = 0
    for c in work_sorted:
        while len(hands[idx % n_shards]) >= sizes[idx % n_shards]:
            idx += 1            # this hand is full of real clusters
        hands[idx % n_shards].append(int(c))
        idx += 1
    return np.concatenate(hands).astype(np.int64)


# Byte-bounded LRU: a dense plan holds the full (K, L, J) Wigner table
# (~1 GB at B = 128), so bounding by COUNT alone (the old max-8 rule) lets
# a paper-scale B-sweep OOM the host.  Entries are (plan, nbytes); eviction
# drops least-recently-used plans until the total fits $REPRO_PLAN_CACHE_BYTES
# (the newest plan is always kept, even if it alone exceeds the bound).
_PLAN_CACHE: collections.OrderedDict = collections.OrderedDict()
_PLAN_CACHE_DEFAULT_BYTES = 2 * 1024 ** 3
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def plan_cache_bytes_limit() -> int:
    """Cache bound in bytes; override with $REPRO_PLAN_CACHE_BYTES."""
    import os
    return int(os.environ.get("REPRO_PLAN_CACHE_BYTES",
                              _PLAN_CACHE_DEFAULT_BYTES))


def _plan_nbytes(plan: SoftPlan) -> int:
    return int(sum(x.size * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(plan)))


def plan_cache_stats() -> dict:
    """Counters + byte accounting for the build_plan memo."""
    return dict(_PLAN_CACHE_STATS,
                plans=len(_PLAN_CACHE),
                bytes=sum(n for _, n in _PLAN_CACHE.values()),
                bytes_limit=plan_cache_bytes_limit())


def _plan_cache_put(key, plan: SoftPlan) -> None:
    _PLAN_CACHE[key] = (plan, _plan_nbytes(plan))
    limit = plan_cache_bytes_limit()
    while len(_PLAN_CACHE) > 1 and \
            sum(n for _, n in _PLAN_CACHE.values()) > limit:
        _PLAN_CACHE.popitem(last=False)
        _PLAN_CACHE_STATS["evictions"] += 1


def build_plan(B: int, dtype=jnp.float64, pad_to: int | None = None,
               order: np.ndarray | None = None,
               streaming: bool = False) -> SoftPlan:
    """Precompute the clustered-DWT plan (paper: 'precomputation of the
    matrices using the three-term recurrence').

    pad_to: pad the cluster axis to a multiple (for even mesh sharding);
    padded rows have sign 0 everywhere and a zero Wigner block.
    order: optional cluster permutation (see shard_balanced_order).
    streaming: build WITHOUT the dense (K, L, J) Wigner table (d=None) --
    neither `wigner.wigner_d_fundamental` nor any other O(B^3)-sized host
    array is touched, so plan construction stays O(K) and paper-scale
    bandwidths (B >= 128) build in milliseconds of host RSS instead of
    gigabytes.  All non-d metadata is byte-identical to the dense build;
    executors that need d reject the plan loudly (see SoftPlan).

    Plans are memoized by (B, dtype, pad_to, order, streaming): benchmarks
    that sweep schedules at a fixed bandwidth reuse one plan (and one Wigner
    table via the wigner.wigner_d_fundamental cache) instead of rebuilding
    it per schedule.  SoftPlan is a frozen dataclass of immutable jnp
    arrays, so sharing is safe.
    """
    key = (B, jnp.dtype(dtype).str, pad_to,
           None if order is None else np.asarray(order).tobytes(),
           bool(streaming))
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        _PLAN_CACHE.move_to_end(key)
        _PLAN_CACHE_STATS["hits"] += 1
        return hit[0]
    _PLAN_CACHE_STATS["misses"] += 1
    tab = clusters_mod.build_cluster_table(B)
    if order is not None:
        tab = _permute_table(tab, np.asarray(order))

    K = tab.n_clusters
    Kp = K if pad_to is None else ((K + pad_to - 1) // pad_to) * pad_to

    def padk(x, fill=0):
        if Kp == len(x):
            return x
        pad = np.full((Kp - len(x),) + x.shape[1:], fill, dtype=x.dtype)
        return np.concatenate([x, pad], axis=0)

    if streaming:
        d = None
    else:
        fund, _ = wigner.wigner_d_fundamental(B)      # (P, L, J) f64
        d = jnp.asarray(padk(fund[tab.fund_row]), dtype=dtype)

    trash = 2 * B - 1
    gm, gmp = padk(tab.gather_m), padk(tab.gather_mp)
    sm, smp = padk(tab.scatter_m, fill=trash), padk(tab.scatter_mp, fill=trash)
    sign = padk(tab.sign)
    plan = SoftPlan(
        B=B,
        table=tab,
        d=d,
        gather_m=jnp.asarray(gm),
        gather_mp=jnp.asarray(gmp),
        scatter_m=jnp.asarray(sm),
        scatter_mp=jnp.asarray(smp),
        sign=jnp.asarray(sign).astype(dtype),
        reflected=jnp.asarray(padk(tab.reflected)),
        bin_src=jnp.asarray(member_sources(gm, gmp, sign, 2 * B)),
        coeff_src=jnp.asarray(member_sources(sm, smp, sign, 2 * B - 1)),
        w=jnp.asarray(quadrature.weights(B), dtype=dtype),
        scale=jnp.asarray((2 * np.arange(B) + 1) / (8 * np.pi * B), dtype=dtype),
        parity=jnp.asarray((-1.0) ** np.arange(B), dtype=dtype),
        n_padded=Kp,
        # canonicalized (x64-disabled truncates f64 -> f32), so streaming
        # and dense builds report the same plan.dtype
        plan_dtype=jnp.empty(0, dtype=dtype).dtype.str,
    )
    _plan_cache_put(key, plan)
    return plan


def member_sources(row, col, sign, n: int) -> np.ndarray:
    """Inverse of a member placement: for each cell (row, col) of an n x n
    layout, the flat member index k*C + c that lands there.

    Valid members (sign != 0) fill distinct cells, so every cell has at
    most one source and a placement is a gather.  Cells no member fills
    (the FFT's Nyquist bins) hold K*C, out of range: the gather's fill."""
    valid = np.flatnonzero(np.asarray(sign).reshape(-1) != 0)
    cell = (np.asarray(row, np.int64) * n + col).reshape(-1)[valid]
    if len(np.unique(cell)) != len(cell):
        raise ValueError("two cluster members share one output cell")
    src = np.full(n * n, sign.size, np.int32)
    src[cell] = valid
    return src


def _permute_table(tab, perm):
    """Reorder every per-cluster array of a ClusterTable."""
    import dataclasses as _dc
    kw = {}
    for f in _dc.fields(tab):
        v = getattr(tab, f.name)
        kw[f.name] = v[perm] if isinstance(v, np.ndarray) and \
            v.ndim >= 1 and len(v) == tab.n_clusters else v
    return clusters_mod.ClusterTable(**kw)


def bucket_boundaries_from_lstart(l_start: np.ndarray, n_shards: int,
                                  n_buckets: int):
    """Static (k0, k1, l0) LOCAL bucket slices for the bucketed DWT.

    l_start: (Kp,) per-cluster first valid degree in the (padded, permuted)
    global order.  Requires shard_balanced_order: every contiguous Kp/n
    block is extent-sorted, so boundaries computed at LOCAL offsets are
    valid for every shard simultaneously (l0 = min over shards)."""
    K = len(l_start)
    kloc = K // n_shards
    per_shard = np.asarray(l_start).reshape(n_shards, kloc)
    bounds = np.linspace(0, kloc, n_buckets + 1).astype(int)
    out = []
    for i in range(n_buckets):
        k0, k1 = int(bounds[i]), int(bounds[i + 1])
        if k0 == k1:
            continue
        l0 = int(per_shard[:, k0:k1].min())
        out.append((k0, k1, l0))
    return tuple(out)


def plan_lstart(plan: SoftPlan) -> np.ndarray:
    """(Kp,) l-start per cluster.  Padded rows get B-1 (their Wigner blocks
    are zero, so any l0 is correct; B-1 maximizes bucket truncation)."""
    l_start = np.full(plan.n_padded, plan.B - 1, np.int32)
    l_start[: plan.n_clusters] = plan.table.rep[:, 0]
    return l_start


def shard_lstart(plan: SoftPlan, n_shards: int) -> np.ndarray:
    """(n_shards, kloc) per-shard l-start blocks in the contiguous layout
    shard_map hands each device.  With shard_balanced_order every row is
    descending in work (ascending l-start after the extent sort), which is
    what the per-local-tile l0 schedules (fused_shard_meta,
    bucket_boundaries_from_lstart) rely on."""
    return plan_lstart(plan).reshape(n_shards, plan.n_padded // n_shards)


@functools.lru_cache(maxsize=32)
def bucket_boundaries(plan: SoftPlan, n_shards: int, n_buckets: int):
    """Memoized by (plan, n_shards, n_buckets) identity -- every consumer
    (make_bucketed_dwt_fn, core.parallel, repro.plan) shares one slice
    table per plan instead of recomputing it per call."""
    return bucket_boundaries_from_lstart(plan_lstart(plan), n_shards,
                                         n_buckets)


def make_bucketed_dwt_fn(plan: SoftPlan, n_shards: int = 1, n_buckets: int = 8):
    """dwt_fn with static l-truncation per extent bucket (paper P3 ragged
    tiling as pure jnp): each bucket contracts only l >= l0 rows, skipping
    the zero triangle (~2.4x fewer FLOPs and d-table bytes at B = 512)."""
    plan.require_dense("make_bucketed_dwt_fn")
    slices = bucket_boundaries(plan, n_shards, n_buckets)
    kloc = plan.n_padded // n_shards

    def fn(p: SoftPlan, rhs):
        # operate per shard-block so slices line up (n_shards=1: one block)
        K, J, C, _ = rhs.shape
        rhs2 = rhs.reshape(n_shards, kloc, J, C * 2)
        d3 = p.d.reshape(n_shards, kloc, p.d.shape[1], J)
        outs = []
        for (k0, k1, l0) in slices:
            o = jnp.einsum("sklj,skjc->sklc", d3[:, k0:k1, l0:, :],
                           rhs2[:, k0:k1], preferred_element_type=p.d.dtype)
            o = jnp.pad(o, ((0, 0), (0, 0), (l0, 0), (0, 0)))
            outs.append(o)
        out = jnp.concatenate(outs, axis=1).reshape(K, -1, C, 2)
        return out

    return fn

def fft_analysis(f):
    """Samples (2B, 2B, 2B) -> S[mbin, j, m'bin]: (2B)^2 * ifft2."""
    n = f.shape[0]
    return (n * n) * jnp.fft.ifft(jnp.fft.ifft(f, axis=0), axis=2)


def fft_synthesis(gbin):
    """g bins (2B, 2B, 2B) -> samples: unnormalized forward fft2."""
    return jnp.fft.fft(jnp.fft.fft(gbin, axis=0), axis=2)


# ---------------------------------------------------------------------------
# beta-slab streaming of the grid FFT stages
#
# Both FFT stages transform axes 0 and 2 only -- the beta axis (j) rides
# along untouched -- so the (2B)^3 grid can be processed in j-slabs with
# BITWISE-identical results: each length-2B 1-D FFT sees exactly the same
# input column whether it is batched over 2B or over a slab's worth of
# columns.  Streaming plans use these paths so the device never holds the
# monolithic S / gbin intermediates (nor the (K, C, J) complex gather
# temporaries) that the dense path materializes.
#
# The only j-coupling in the surrounding gathers is the beta
# reflection: a reflected member's output slab [j0, j1) reads the MIRROR
# slab [J-j1, J-j0) reversed.  Slab bounds need no symmetry for that --
# the mirror slab's FFT is computed directly from the matching f slab.
# ---------------------------------------------------------------------------

GRID_N_SLABS = 4


def _slab_bounds(J: int, n_slabs: int = GRID_N_SLABS):
    cuts = np.linspace(0, J, min(n_slabs, J) + 1).astype(int)
    return [(int(cuts[i]), int(cuts[i + 1])) for i in range(len(cuts) - 1)
            if cuts[i] < cuts[i + 1]]


def fft_analysis_slab(f, j0: int, j1: int):
    """fft_analysis restricted to beta rows [j0, j1): bitwise equal to
    fft_analysis(f)[:, j0:j1, :] without forming the full S."""
    n = f.shape[0]
    return (n * n) * jnp.fft.ifft(jnp.fft.ifft(f[:, j0:j1, :], axis=0),
                                  axis=2)


def _gather_rhs_slab(plan: SoftPlan, S_direct, S_mirror, j0: int, j1: int):
    """rhs[:, j0:j1] from the direct S slab [j0, j1) and its mirror slab
    [J-j1, J-j0) (reversed for reflected members)."""
    direct = S_direct[plan.gather_m, :, plan.gather_mp]       # (K, C, js)
    mirror = S_mirror[plan.gather_m, :, plan.gather_mp][..., ::-1]
    Sm = jnp.where(plan.reflected[..., None], mirror, direct)
    Sm = Sm * (plan.sign[..., None] * plan.w[None, None, j0:j1])
    rhs = jnp.stack([Sm.real, Sm.imag], axis=-1)              # (K, C, js, 2)
    return jnp.swapaxes(rhs, 1, 2)                            # (K, js, C, 2)


def streamed_rhs(plan: SoftPlan, f):
    """FFT-analysis + gather, streamed in beta slabs: bitwise equal to
    _gather_rhs(plan, fft_analysis(f)) with O((2B)^2 * slab) intermediates."""
    J = 2 * plan.B
    parts = []
    for j0, j1 in _slab_bounds(J):
        S_direct = fft_analysis_slab(f, j0, j1)
        S_mirror = fft_analysis_slab(f, J - j1, J - j0)
        parts.append(_gather_rhs_slab(plan, S_direct, S_mirror, j0, j1))
    return jnp.concatenate(parts, axis=1)


def streamed_synthesis(plan: SoftPlan, gc):
    """Bin placement + FFT-synthesis, streamed in beta slabs: bitwise
    equal to fft_synthesis(_place_bins(plan, gc)) without the monolithic
    (2B, 2B, 2B) bin buffer."""
    J = 2 * plan.B
    parts = []
    for j0, j1 in _slab_bounds(J):
        direct = gc[:, j0:j1, :]
        mirror = gc[:, J - j1:J - j0, :][:, ::-1, :]
        gs = jnp.where(plan.reflected[:, None, :], mirror, direct)
        parts.append(fft_synthesis(_place_bins_nomirror(plan, gs)))
    return jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# stage 2: clustered DWT (forward) / iDWT (inverse)
# ---------------------------------------------------------------------------

def _gather_rhs(plan: SoftPlan, S):
    """Build rhs[k, j, c, ri] from S[mbin, j, m'bin] (complex).

    rhs column c of cluster k = sign * w * S(member), with j reversed for
    beta-reflected members.
    """
    # S gathered at member bins: (K, C, J) complex
    Sm = S[plan.gather_m, :, plan.gather_mp]
    Sm = jnp.where(plan.reflected[..., None], Sm[..., ::-1], Sm)
    Sm = Sm * (plan.sign[..., None] * plan.w[None, None, :])
    rhs = jnp.stack([Sm.real, Sm.imag], axis=-1)     # (K, C, J, 2)
    return jnp.swapaxes(rhs, 1, 2)                    # (K, J, C, 2)


def dwt_apply(plan: SoftPlan, rhs):
    """The clustered DWT contraction: (K,L,J) x (K,J,C,2) -> (K,L,C,2).

    Kept as its own function: this is the compute hot-spot the Pallas kernel
    (kernels/dwt.py) replaces 1:1.
    """
    d = plan.require_dense("dwt_apply")
    C2 = rhs.shape[2] * rhs.shape[3]
    out = jnp.einsum("klj,kjc->klc", d,
                     rhs.reshape(rhs.shape[0], rhs.shape[1], C2),
                     preferred_element_type=d.dtype)
    return out.reshape(out.shape[0], out.shape[1], rhs.shape[2], rhs.shape[3])


def idwt_apply(plan: SoftPlan, lhs):
    """The clustered iDWT contraction: (K,L,J) x (K,L,C,2) -> (K,J,C,2)."""
    d = plan.require_dense("idwt_apply")
    C2 = lhs.shape[2] * lhs.shape[3]
    out = jnp.einsum("klj,klc->kjc", d,
                     lhs.reshape(lhs.shape[0], lhs.shape[1], C2),
                     preferred_element_type=d.dtype)
    return out.reshape(out.shape[0], out.shape[1], lhs.shape[2], lhs.shape[3])


def place_members(src, rows):
    """Gather complex member rows (N, n) into the cells of ``src``, an index
    table of :func:`member_sources`: (len(src), n), 0 where a cell has no
    member.  One gather moves both parts, each row laid out [Re | Im] so
    that it is lane-dense."""
    n = rows.shape[-1]
    ri = jnp.concatenate([rows.real, rows.imag], axis=-1)      # (N, 2n)
    out = ri.at[src].get(mode="fill", fill_value=0,
                         wrap_negative_indices=False)
    return jax.lax.complex(out[:, :n], out[:, n:])


def place_coeffs(plan: SoftPlan, out):
    """out[k, l, c] (complex) -> dense coefficients (L, 2B-1, 2B-1)."""
    n = 2 * plan.B - 1
    rows = jnp.swapaxes(out, 1, 2).reshape(-1, out.shape[1])   # (K*C, L)
    return place_members(plan.coeff_src, rows).T.reshape(-1, n, n)


def _output_coeffs(plan: SoftPlan, out):
    """out[k, l, c] (complex) -> the dense coefficient layout, with the
    output sign (-1)^l of reflected members and scale (2l+1)/(8 pi B)."""
    sgn = jnp.where(plan.reflected[:, None, :], plan.parity[None, :, None],
                    jnp.ones((), plan.parity.dtype))
    return place_coeffs(plan, out * (sgn * plan.scale[None, :, None]))


def _gather_coeffs(plan: SoftPlan, fhat):
    """Gather lhs[k, l, c] = sign * (-1)^{l if reflected} * fhat(member)."""
    B = plan.B
    fpad = jnp.pad(fhat, ((0, 0), (0, 1), (0, 1)))   # trash cell reads 0
    lhs = fpad[:, plan.scatter_m, plan.scatter_mp]   # (L, K, C)
    lhs = jnp.moveaxis(lhs, 0, 1)                     # (K, L, C)
    sgn = jnp.where(plan.reflected[:, None, :], plan.parity[None, :, None],
                    jnp.ones((), plan.parity.dtype))
    lhs = lhs * (sgn * plan.sign[:, None, :])
    return jnp.stack([lhs.real, lhs.imag], axis=-1)  # (K, L, C, 2)


def _place_bins_nomirror(plan: SoftPlan, g):
    """FFT bins (2B, j, 2B) of g[k, j, c] (complex, reflection already
    applied): each bin gathers its member's column (plan.bin_src), the
    Nyquist bins read 0.  j-independent, so slab callers pass partial-j g."""
    n = 2 * plan.B
    rows = jnp.swapaxes(g, 1, 2).reshape(-1, g.shape[1])       # (K*C, j)
    bins = place_members(plan.bin_src, rows).reshape(n, n, -1)
    return bins.transpose(0, 2, 1)


def _place_bins(plan: SoftPlan, g):
    """FFT bins (2B, j, 2B) of g[k, j, c] (complex)."""
    g = jnp.where(plan.reflected[:, None, :], g[:, ::-1, :], g)
    return _place_bins_nomirror(plan, g)


# ---------------------------------------------------------------------------
# full transforms
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=())
def _forward_jit(plan: SoftPlan, f):
    S = fft_analysis(f)
    rhs = _gather_rhs(plan, S)
    out = dwt_apply(plan, rhs)
    outc = out[..., 0] + 1j * out[..., 1]
    return _output_coeffs(plan, outc)


def _require_recurrence_fn(plan: SoftPlan, fn, which: str):
    if plan.streaming and fn is None:
        raise ValueError(
            f"streaming plan (B={plan.B}, d=None) has no dense Wigner table "
            f"for the jnp einsum fallback; pass a recurrence-family "
            f"{which} (kernels.ops.make_{which}(..., impl='fused'/'onthefly'))")


def forward_clustered(plan: SoftPlan, f, dwt_fn=None):
    """FSOFT via the clustered DWT.  `dwt_fn` lets callers swap in the
    Pallas kernel (same (plan, rhs) -> out contract).

    Streaming plans route the FFT+gather stage through beta slabs
    (streamed_rhs) -- bitwise-identical output, no monolithic grid
    intermediate -- and require a recurrence-family dwt_fn."""
    _require_recurrence_fn(plan, dwt_fn, "dwt_fn")
    if dwt_fn is None:
        return _forward_jit(plan, f)
    rhs = streamed_rhs(plan, f) if plan.streaming \
        else _gather_rhs(plan, fft_analysis(f))
    out = dwt_fn(plan, rhs)
    outc = out[..., 0] + 1j * out[..., 1]
    return _output_coeffs(plan, outc)


@partial(jax.jit, static_argnums=())
def _inverse_jit(plan: SoftPlan, fhat):
    lhs = _gather_coeffs(plan, fhat)
    g = idwt_apply(plan, lhs)
    gc = g[..., 0] + 1j * g[..., 1]
    gbin = _place_bins(plan, gc)
    return fft_synthesis(gbin)


def inverse_clustered(plan: SoftPlan, fhat, idwt_fn=None):
    """iFSOFT via the clustered iDWT.  Streaming plans scatter + synthesize
    in beta slabs (streamed_synthesis); see forward_clustered."""
    _require_recurrence_fn(plan, idwt_fn, "idwt_fn")
    if idwt_fn is None:
        return _inverse_jit(plan, fhat)
    lhs = _gather_coeffs(plan, fhat)
    g = idwt_fn(plan, lhs)
    gc = g[..., 0] + 1j * g[..., 1]
    if plan.streaming:
        return streamed_synthesis(plan, gc)
    return fft_synthesis(_place_bins(plan, gc))


# ---------------------------------------------------------------------------
# multi-transform batching: V rotations through ONE DWT launch
# ---------------------------------------------------------------------------

def forward_clustered_batch(plan: SoftPlan, f, dwt_fn=None):
    """FSOFT of a batch: f (V, 2B, 2B, 2B) -> coefficients (V, B, 2B-1,
    2B-1).

    The FFT stage and the gather/scatter run vmapped (XLA batches them);
    the DWT contraction takes the whole (V, K, J, C, 2) stack at once, so a
    batch-aware dwt_fn (ops.make_dwt_fn(..., batch=V)) packs the V
    transforms onto the kernel's lane axis and launches ONCE -- at V = 4
    the per-transform launch + Wigner-generation cost drops ~4x (the d-rows
    are reused across all V lanes).  dwt_fn=None falls back to a vmapped
    einsum (pure jnp, differentiable).
    """
    _require_recurrence_fn(plan, dwt_fn, "dwt_fn")
    if plan.streaming:
        rhs = jax.vmap(lambda ff: streamed_rhs(plan, ff))(f)
    else:
        S = jax.vmap(fft_analysis)(f)
        rhs = jax.vmap(lambda s: _gather_rhs(plan, s))(S)  # (V, K, J, C, 2)
    if dwt_fn is None:
        out = jax.vmap(lambda r: dwt_apply(plan, r))(rhs)
    else:
        out = dwt_fn(plan, rhs)                          # (V, K, L, C, 2)
    outc = out[..., 0] + 1j * out[..., 1]
    return jax.vmap(lambda o: _output_coeffs(plan, o))(outc)


def inverse_clustered_batch(plan: SoftPlan, fhat, idwt_fn=None):
    """iFSOFT of a batch: fhat (V, B, 2B-1, 2B-1) -> samples (V, 2B, 2B,
    2B).  idwt_fn must be batch-aware when given (ops.make_idwt_fn(...,
    batch=V)); see forward_clustered_batch."""
    _require_recurrence_fn(plan, idwt_fn, "idwt_fn")
    lhs = jax.vmap(lambda h: _gather_coeffs(plan, h))(fhat)  # (V, K, L, C, 2)
    if idwt_fn is None:
        g = jax.vmap(lambda x: idwt_apply(plan, x))(lhs)
    else:
        g = idwt_fn(plan, lhs)                            # (V, K, J, C, 2)
    gc = g[..., 0] + 1j * g[..., 1]
    if plan.streaming:
        return jax.vmap(lambda x: streamed_synthesis(plan, x))(gc)
    gbin = jax.vmap(lambda x: _place_bins(plan, x))(gc)
    return jax.vmap(fft_synthesis)(gbin)
