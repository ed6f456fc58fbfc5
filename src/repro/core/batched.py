"""Clustered / batched FSOFT & iFSOFT -- the TPU-native formulation.

This module reshapes the paper's parallel design into dense array programs:
the whole DWT stage (all clusters) becomes ONE batched contraction

    forward :  out[k, c, l] = sum_j  d[k, l, j] * rhs[k, c, j]
    inverse :  g[k, c, j]   = sum_l  d[k, l, j] * lhs[k, c, l]

where k runs over symmetry clusters (paper's work packages, kappa-ordered),
c over the <= 8 cluster members' real and imaginary rows, and d is the
fundamental-domain Wigner table.  Gather/sign metadata comes from
:mod:`clusters`; the inverse index tables that place members into FFT
bins and dense coefficients are built with the plan
(:func:`member_sources`).

The same plan drives
  * the pure-jnp path below (runs anywhere, differentiable),
  * the shard_map-distributed path (:mod:`parallel`) -- shard over k,
  * the fused Pallas DWT kernels (:mod:`repro.kernels.dwt_fused`) -- grid
    over k tiles, Wigner rows generated on the fly.

Complex arithmetic is carried as real MEMBER ROWS so the heavy contraction
is a real matmul (MXU-friendly; complex einsum would promote the real
Wigner operand and double the FLOPs).  A cluster's operand is (C*2, A):
member c's real part in row 2c, its imaginary part in row 2c+1, and the
beta samples (A = J) or degrees (A = L) along the row.  On a TPU the C*2
rows sit on the sublanes and A on the 128 lanes, so every array between
the grid stages and the kernels is dense under the (8, 128) tiling; and
(K, C*2, A) is, byte for byte, the (K*C, 2A) [Re | Im] rows that the
placement gathers read.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from . import clusters as clusters_mod
from . import quadrature, soft, wigner

__all__ = ["SoftPlan", "build_plan", "plan_cache_stats",
           "fft_analysis_slab", "streamed_rhs", "streamed_synthesis",
           "member_rows",
           "forward_clustered", "inverse_clustered",
           "forward_clustered_batch", "inverse_clustered_batch"]


@dataclasses.dataclass(frozen=True, eq=False)
class SoftPlan:
    """Device-ready tables for the clustered transforms.

    All arrays are jnp; shapes use K = #clusters (padded to `pad_to` if
    given), L = B, J = 2B, C = 8 member slots.

    ``d is None`` marks a STREAMING plan (build_plan(streaming=True)):
    the dense (K, L, J) Wigner table is never materialized -- on the
    host or anywhere else -- and the fused kernel family (seeded from
    ``table.rep``) is the only executor.  The dense-table consumer (the
    reference einsum) rejects streaming plans loudly.
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
                f"table was never materialized).  Use the fused "
                f"kernels (impl='fused') or rebuild with "
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
    local block supports the fused kernels' per-tile l-truncation
    (core.parallel.fused_shard_meta).

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


def plan_lstart(plan: SoftPlan) -> np.ndarray:
    """(Kp,) l-start per cluster.  Padded rows get B-1 (their Wigner blocks
    are zero, so any l0 is correct; B-1 maximizes tile truncation)."""
    l_start = np.full(plan.n_padded, plan.B - 1, np.int32)
    l_start[: plan.n_clusters] = plan.table.rep[:, 0]
    return l_start


def shard_lstart(plan: SoftPlan, n_shards: int) -> np.ndarray:
    """(n_shards, kloc) per-shard l-start blocks in the contiguous layout
    shard_map hands each device.  With shard_balanced_order every row is
    descending in work (ascending l-start after the extent sort), which is
    what the per-local-tile l0 schedule (fused_shard_meta) relies on."""
    return plan_lstart(plan).reshape(n_shards, plan.n_padded // n_shards)


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
    return member_rows(Sm * (plan.sign[..., None] * plan.w[None, None, j0:j1]))


def streamed_rhs(plan: SoftPlan, f):
    """FFT-analysis + gather, streamed in beta slabs: bitwise equal to
    _gather_rhs(plan, fft_analysis(f)) with O((2B)^2 * slab) intermediates."""
    J = 2 * plan.B
    parts = []
    for j0, j1 in _slab_bounds(J):
        S_direct = fft_analysis_slab(f, j0, j1)
        S_mirror = fft_analysis_slab(f, J - j1, J - j0)
        parts.append(_gather_rhs_slab(plan, S_direct, S_mirror, j0, j1))
    return jnp.concatenate(parts, axis=2)


def streamed_synthesis(plan: SoftPlan, g):
    """Bin placement + FFT-synthesis, streamed in beta slabs: bitwise
    equal to fft_synthesis(_place_bins(plan, g)) without the monolithic
    (2B, 2B, 2B) bin buffer.  g: the iDWT's member rows (K, C*2, J)."""
    J = 2 * plan.B
    refl = _row_mask(plan.reflected)
    parts = []
    for j0, j1 in _slab_bounds(J):
        direct = g[:, :, j0:j1]
        mirror = g[:, :, J - j1:J - j0][..., ::-1]
        gs = jnp.where(refl, mirror, direct)
        parts.append(fft_synthesis(_place_bins_nomirror(plan, gs)))
    return jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# stage 2: clustered DWT (forward) / iDWT (inverse)
# ---------------------------------------------------------------------------

def member_rows(x):
    """Complex members (K, C, A) -> real member rows (K, C*2, A): member
    c's real part in row 2c, its imaginary part in row 2c+1."""
    K, C, A = x.shape
    return jnp.stack([x.real, x.imag], axis=2).reshape(K, 2 * C, A)


def _row_mask(mask):
    """A per-member (K, C) mask -> (K, C*2, 1), over member rows."""
    return jnp.repeat(mask, 2, axis=1)[:, :, None]


def _gather_rhs(plan: SoftPlan, S):
    """Build the DWT's member rows rhs (K, C*2, J) from S[mbin, j, m'bin]
    (complex).

    Member c of cluster k reads sign * w * S(member), with j reversed for
    beta-reflected members.
    """
    # S gathered at member bins: (K, C, J) complex
    Sm = S[plan.gather_m, :, plan.gather_mp]
    Sm = jnp.where(plan.reflected[..., None], Sm[..., ::-1], Sm)
    return member_rows(Sm * (plan.sign[..., None] * plan.w[None, None, :]))


def dwt_apply(plan: SoftPlan, rhs):
    """The clustered DWT contraction: (K,L,J) x (K,C2,J) -> (K,C2,L).

    Kept as its own function: this is the compute hot-spot the fused
    Pallas kernels (kernels/dwt_fused.py) replace 1:1.
    """
    d = plan.require_dense("dwt_apply")
    return jnp.einsum("klj,kcj->kcl", d, rhs, preferred_element_type=d.dtype)


def idwt_apply(plan: SoftPlan, lhs):
    """The clustered iDWT contraction: (K,L,J) x (K,C2,L) -> (K,C2,J)."""
    d = plan.require_dense("idwt_apply")
    return jnp.einsum("klj,kcl->kcj", d, lhs, preferred_element_type=d.dtype)


def place_members(src, rows):
    """Gather member rows (N, 2n), each laid out [Re | Im] so that it is
    lane-dense, into the cells of ``src``, an index table of
    :func:`member_sources`: complex (len(src), n), 0 where a cell has no
    member.  One gather moves both parts."""
    n = rows.shape[-1] // 2
    out = rows.at[src].get(mode="fill", fill_value=0,
                           wrap_negative_indices=False)
    return jax.lax.complex(out[:, :n], out[:, n:])


def place_coeffs(plan: SoftPlan, rows):
    """Member rows (K, C*2, L) -> dense coefficients (L, 2B-1, 2B-1)."""
    n = 2 * plan.B - 1
    K, C2, L = rows.shape
    cells = place_members(plan.coeff_src, rows.reshape(K * C2 // 2, 2 * L))
    return cells.T.reshape(-1, n, n)


def _output_coeffs(plan: SoftPlan, out):
    """The DWT's member rows (K, C*2, L) -> the dense coefficient layout,
    with the output sign (-1)^l of reflected members and scale
    (2l+1)/(8 pi B)."""
    sgn = jnp.where(_row_mask(plan.reflected), plan.parity[None, None, :],
                    jnp.ones((), plan.parity.dtype))
    return place_coeffs(plan, out * (sgn * plan.scale[None, None, :]))


def _gather_coeffs(plan: SoftPlan, fhat):
    """The iDWT's member rows lhs (K, C*2, L): member c of cluster k reads
    sign * (-1)^{l if reflected} * fhat(member).  A row gather from the
    coefficients laid out as (m, m') rows of [Re | Im] over l."""
    L, n = fhat.shape[0], fhat.shape[1]
    K, C = plan.sign.shape
    ft = fhat.reshape(L, n * n).T                             # (n*n, L)
    rows = jnp.concatenate([ft.real, ft.imag], axis=-1)       # (n*n, 2L)
    src = jnp.where(plan.sign != 0, plan.scatter_m * n + plan.scatter_mp,
                    n * n)                                    # unused: fill
    lhs = rows.at[src.reshape(-1)].get(mode="fill", fill_value=0,
                                       wrap_negative_indices=False)
    sgn = jnp.where(_row_mask(plan.reflected), plan.parity[None, None, :],
                    jnp.ones((), plan.parity.dtype))
    return lhs.reshape(K, 2 * C, L) * (sgn * _row_mask(plan.sign))


def _place_bins_nomirror(plan: SoftPlan, g):
    """FFT bins (2B, j, 2B) of the iDWT's member rows g (K, C*2, j)
    (reflection already applied): each bin gathers its member's row
    (plan.bin_src), the Nyquist bins read 0.  j-independent, so slab
    callers pass partial-j g."""
    n = 2 * plan.B
    K, C2, j = g.shape
    bins = place_members(plan.bin_src, g.reshape(K * C2 // 2, 2 * j))
    return bins.reshape(n, n, j).transpose(0, 2, 1)


def _place_bins(plan: SoftPlan, g):
    """FFT bins (2B, j, 2B) of the iDWT's member rows g (K, C*2, J)."""
    g = jnp.where(_row_mask(plan.reflected), g[..., ::-1], g)
    return _place_bins_nomirror(plan, g)


# ---------------------------------------------------------------------------
# full transforms
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=())
def _forward_jit(plan: SoftPlan, f):
    S = fft_analysis(f)
    rhs = _gather_rhs(plan, S)
    return _output_coeffs(plan, dwt_apply(plan, rhs))


def _require_recurrence_fn(plan: SoftPlan, fn, which: str):
    if plan.streaming and fn is None:
        raise ValueError(
            f"streaming plan (B={plan.B}, d=None) has no dense Wigner table "
            f"for the jnp einsum fallback; pass a fused-family "
            f"{which} (kernels.ops.make_{which})")


def forward_clustered(plan: SoftPlan, f, dwt_fn=None):
    """FSOFT via the clustered DWT.  `dwt_fn` lets callers swap in the
    Pallas kernel (same (plan, rhs) -> out contract).

    Streaming plans route the FFT+gather stage through beta slabs
    (streamed_rhs) -- bitwise-identical output, no monolithic grid
    intermediate -- and require a fused-family dwt_fn."""
    _require_recurrence_fn(plan, dwt_fn, "dwt_fn")
    if dwt_fn is None:
        return _forward_jit(plan, f)
    rhs = streamed_rhs(plan, f) if plan.streaming \
        else _gather_rhs(plan, fft_analysis(f))
    return _output_coeffs(plan, dwt_fn(plan, rhs))


@partial(jax.jit, static_argnums=())
def _inverse_jit(plan: SoftPlan, fhat):
    lhs = _gather_coeffs(plan, fhat)
    return fft_synthesis(_place_bins(plan, idwt_apply(plan, lhs)))


def inverse_clustered(plan: SoftPlan, fhat, idwt_fn=None):
    """iFSOFT via the clustered iDWT.  Streaming plans scatter + synthesize
    in beta slabs (streamed_synthesis); see forward_clustered."""
    _require_recurrence_fn(plan, idwt_fn, "idwt_fn")
    if idwt_fn is None:
        return _inverse_jit(plan, fhat)
    g = idwt_fn(plan, _gather_coeffs(plan, fhat))
    if plan.streaming:
        return streamed_synthesis(plan, g)
    return fft_synthesis(_place_bins(plan, g))


# ---------------------------------------------------------------------------
# multi-transform batching: V rotations through ONE DWT launch
# ---------------------------------------------------------------------------

def forward_clustered_batch(plan: SoftPlan, f, dwt_fn=None):
    """FSOFT of a batch: f (V, 2B, 2B, 2B) -> coefficients (V, B, 2B-1,
    2B-1).

    The FFT stage and the gather/scatter run vmapped (XLA batches them);
    the DWT contraction takes the whole (V, K, C*2, J) stack at once, so a
    batch-aware dwt_fn (ops.make_dwt_fn(..., batch=V)) packs the V
    transforms onto the kernel's C2 axis and launches ONCE -- at V = 4
    the per-transform launch + Wigner-generation cost drops ~4x (the d-rows
    are reused across all V lanes).  dwt_fn=None falls back to a vmapped
    einsum (pure jnp, differentiable).
    """
    _require_recurrence_fn(plan, dwt_fn, "dwt_fn")
    if plan.streaming:
        rhs = jax.vmap(lambda ff: streamed_rhs(plan, ff))(f)
    else:
        S = jax.vmap(fft_analysis)(f)
        rhs = jax.vmap(lambda s: _gather_rhs(plan, s))(S)  # (V, K, C2, J)
    if dwt_fn is None:
        out = jax.vmap(lambda r: dwt_apply(plan, r))(rhs)
    else:
        out = dwt_fn(plan, rhs)                          # (V, K, C2, L)
    return jax.vmap(lambda o: _output_coeffs(plan, o))(out)


def inverse_clustered_batch(plan: SoftPlan, fhat, idwt_fn=None):
    """iFSOFT of a batch: fhat (V, B, 2B-1, 2B-1) -> samples (V, 2B, 2B,
    2B).  idwt_fn must be batch-aware when given (ops.make_idwt_fn(...,
    batch=V)); see forward_clustered_batch."""
    _require_recurrence_fn(plan, idwt_fn, "idwt_fn")
    lhs = jax.vmap(lambda h: _gather_coeffs(plan, h))(fhat)  # (V, K, C2, L)
    if idwt_fn is None:
        g = jax.vmap(lambda x: idwt_apply(plan, x))(lhs)
    else:
        g = idwt_fn(plan, lhs)                            # (V, K, C2, J)
    if plan.streaming:
        return jax.vmap(lambda x: streamed_synthesis(plan, x))(g)
    gbin = jax.vmap(lambda x: _place_bins(plan, x))(g)
    return jax.vmap(fft_synthesis)(gbin)
