"""Rotational correlation on SO(3) via batched inverse FFTs.

The correlation theorem (PAPER.md Sec. 1; Kovacs & Wriggers 2002): for
f, g bandlimited on S^2 with coefficient vectors f_l, g_l,

    C(R) = sum_l <f_l, D^l(R) g_l> = sum_{l,m,m'} conj(f[l,m]) D^l_{mm'}(R)
           g[l,m']

so ALL (2B)^3 grid correlations are ONE inverse SO(3) FFT of the
outer-product coefficient array T[l, m, m'] = conj(f[l, m]) g[l, m'].
The engine below evaluates batches of such T through a
:class:`repro.plan.Transform`'s lane-packed ``inverse_batch`` executor:
the plan resolves the iDWT schedule and the lane width V (autotuned /
VMEM-guarded by ``repro.plan``), and V correlation problems ride one
kernel launch, each on-the-fly Wigner row reused V times.

Request shapes served:

  * :meth:`CorrelationEngine.match`       -- one (f, g) pair
  * :meth:`CorrelationEngine.match_bank`  -- one query vs a template bank
  * :meth:`CorrelationEngine.match_batch` -- many independent pairs

Inputs can be S^2 coefficient vectors (B, 2B-1) or raw grid samples
(2B, 2B) -- samples enter through :func:`repro.so3.s2.s2_analysis`.
Batches are zero-padded to the plan's lane width (one compiled kernel
shape, predictable latency); ``stats`` tracks launches, lane occupancy,
and padding waste.

A bank query never brings a grid to the host: the bank is uploaded once
(:meth:`CorrelationEngine.load_bank`), and a query is one jitted loop on
the device over chunks of V templates -- pair coefficients, the plan's
lane-packed inverse, the peak search of :func:`repro.kernels.peaks.
grid_peaks` and a gather of the peak's six axis neighbours -- so the host
reads back a few bytes a template and refines them all at once.

Every :class:`MatchResult` carries both the raw correlation ``peak`` and
the normalized cross-correlation ``score`` = peak / (||f|| ||g||) (the
coefficient 2-norms).  By Cauchy-Schwarz the score lies in [-1, 1] with
1 iff f is exactly a rotation of g -- one-vs-bank ranking uses it so
peaks stay comparable across templates of different power.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import quadrature, soft
from repro.kernels import peaks

from . import s2

__all__ = ["MatchResult", "TemplateBank", "CorrelationEngine", "correlate",
           "angle_error", "random_rotation", "result_key"]


def result_key(res: "MatchResult") -> tuple:
    """Bitwise-comparable fingerprint of a MatchResult: the grid argmax
    plus the exact float bit patterns of the refined angles, peak, and
    score.  Two results are the same computation iff their keys are
    equal -- the serving tier's parity oracle (benchmarks/serve_load.py)
    and the mixed-bandwidth fuzz tests compare batched-lane results
    against direct unbatched execution with this, so a lane packing that
    perturbs even the last ulp of any field is caught."""
    def bits(x):
        return None if x is None else float(x).hex()
    return (res.index, bits(res.alpha), bits(res.beta), bits(res.gamma),
            bits(res.peak), bits(res.score))


def angle_error(est: float, true: float) -> float:
    """Distance between two angles on the circle (shared by the demo,
    benchmarks, and tests -- recovery errors are always reported this way)."""
    d = abs(est - true) % (2 * np.pi)
    return min(d, 2 * np.pi - d)


def random_rotation(seed_or_rng=0, beta_margin: float = 0.2):
    """Random ZYZ Euler angles with beta kept `beta_margin` clear of the
    (0, pi) endpoints (where wigner_d_table's log-domain seeds are
    undefined and the rotation parametrization degenerates).  The shared
    hidden-rotation sampler for the demo, benchmarks, and tests."""
    rng = (seed_or_rng if isinstance(seed_or_rng, np.random.Generator)
           else np.random.default_rng(seed_or_rng))
    return (float(rng.uniform(0, 2 * np.pi)),
            float(rng.uniform(beta_margin, np.pi - beta_margin)),
            float(rng.uniform(0, 2 * np.pi)))


@dataclasses.dataclass(frozen=True)
class MatchResult:
    """One recovered rotation: Euler angles (ZYZ, repo convention), the
    raw correlation peak, the grid argmax, and the normalized
    cross-correlation score (peak / (||f|| ||g||), in [-1, 1]; None when
    the norms were unavailable or zero).  ``stencil`` holds Re C at the
    peak's six axis neighbours the refinement read (order of
    :data:`STENCIL` after the peak); it takes no part in equality."""

    alpha: float
    beta: float
    gamma: float
    peak: float
    index: tuple[int, int, int]
    score: float | None = None
    stencil: tuple[float, ...] | None = dataclasses.field(default=None,
                                                          compare=False)

    @property
    def euler(self) -> tuple[float, float, float]:
        return (self.alpha, self.beta, self.gamma)

    @property
    def rank_key(self) -> float:
        """Cross-template ranking value: the normalized score when
        available, else the raw peak."""
        return self.peak if self.score is None else self.score


# The peak and its six axis neighbours, in the order every stencil array
# holds them.  alpha and gamma wrap around; beta stops at the grid's edges,
# where the neighbour outside is the peak itself and beta is not refined.
STENCIL = ("peak", "alpha-", "alpha+", "beta-", "beta+", "gamma-", "gamma+")


def stencil_ijk(i, j, k, n: int, xp=np):
    """Grid indices (each (..., 7)) of the stencil around peaks (i, j, k)
    of an (n, n, n) grid; ``xp`` is numpy or jax.numpy."""
    jm, jp = xp.maximum(j - 1, 0), xp.minimum(j + 1, n - 1)
    im, ip = (i - 1) % n, (i + 1) % n
    km, kp = (k - 1) % n, (k + 1) % n
    return (xp.stack([i, im, ip, i, i, i, i], axis=-1),
            xp.stack([j, j, j, jm, jp, j, j], axis=-1),
            xp.stack([k, k, k, k, k, km, kp], axis=-1))


def _parabolic_offset(ym, y0, yp):
    """Sub-grid offsets of quadratics through three equispaced samples,
    elementwise over arrays, clamped to half a grid step (0 where the
    stencil is degenerate).  Computed in the samples' own precision."""
    den = ym - 2.0 * y0 + yp
    ok = (den != 0.0) & np.isfinite(den)
    with np.errstate(divide="ignore", invalid="ignore"):
        off = np.clip(0.5 * (ym - yp) / np.where(ok, den, 1.0), -0.5, 0.5)
    return np.where(ok, off, 0.0).astype(np.float64)


def refine_stencils(B: int, index: np.ndarray, stencil: np.ndarray,
                    norms=None, refine: bool = True) -> list[MatchResult]:
    """MatchResults of many peaks at once.

    index: (N, 3) grid indices of the peaks; stencil: (N, 7) Re C at the
    points of :data:`STENCIL`; norms: (N,) ||f|| ||g|| per pair, or None.
    refine=True fits a 1-D quadratic per axis through the peak (periodic
    on alpha/gamma; beta is not refined at the grid edges), pushing the
    error below the pi/B grid resolution for well-separated peaks.
    """
    index = np.asarray(index).reshape(-1, 3)
    stencil = np.asarray(stencil).reshape(-1, len(STENCIL))
    i, j, k = index.T
    a = quadrature.alphas(B)[i]
    b = quadrature.betas(B)[j]
    g = quadrature.gammas(B)[k]
    if refine:
        n = 2 * B
        y0, am, ap, bm, bp, gm, gp = stencil.T
        a = (a + (np.pi / B) * _parabolic_offset(am, y0, ap)) % (2 * np.pi)
        g = (g + (np.pi / B) * _parabolic_offset(gm, y0, gp)) % (2 * np.pi)
        inner = (0 < j) & (j < n - 1)
        b = b + (np.pi / (2 * B)) * np.where(
            inner, _parabolic_offset(bm, y0, bp), 0.0)
    norms = [None] * len(index) if norms is None else np.asarray(
        norms, np.float64).tolist()
    return [MatchResult(alpha=ar, beta=br, gamma=gr, peak=s[0],
                        index=tuple(ix), score=s[0] / nr if nr else None,
                        stencil=tuple(s[1:]))
            for ar, br, gr, s, ix, nr in zip(
                np.asarray(a, np.float64).tolist(),
                np.asarray(b, np.float64).tolist(),
                np.asarray(g, np.float64).tolist(),
                np.asarray(stencil).tolist(), index.tolist(), norms)]


def peak_euler(C: np.ndarray, B: int, refine: bool = True,
               norm: float | None = None) -> MatchResult:
    """Argmax of Re C over the (2B)^3 Euler grid -> MatchResult, refined
    as :func:`refine_stencils` says.  `norm` = ||f|| ||g|| of the
    correlated pair; when given (and nonzero) the result carries score =
    peak / norm.
    """
    Cr = np.asarray(C).real
    ijk = np.unravel_index(int(np.argmax(Cr)), Cr.shape)
    stencil = Cr[stencil_ijk(*ijk, 2 * B)]
    return refine_stencils(B, [ijk], stencil, None if norm is None else
                           [norm], refine)[0]


@dataclasses.dataclass(frozen=True, eq=False)
class TemplateBank:
    """A template bank held on the device between queries
    (:meth:`CorrelationEngine.load_bank`): the S^2 coefficients in chunks
    of the engine's lane width V, zero past the last template, and each
    template's coefficient 2-norm on the host."""

    coeffs: jax.Array          # (chunks, V, B, 2B-1)
    norms: np.ndarray          # (templates,) ||g_m||

    def __len__(self) -> int:
        return len(self.norms)


def pair_norm(f, g) -> float:
    """||f|| ||g|| over the coefficient vectors -- the normalizer that
    makes correlation peaks comparable across templates (NCC score)."""
    return float(jnp.linalg.norm(f)) * float(jnp.linalg.norm(g))


class CorrelationEngine:
    """Batched SO(3) correlation at one bandwidth, executing on a
    :class:`repro.plan.Transform`.

    Preferred construction is from a plan -- ``repro.plan(B).engine()``
    or ``CorrelationEngine(transform=t)`` -- so the engine inherits the
    plan's resolved schedule and lane width V.  The legacy keyword form
    ``CorrelationEngine(B, lane_width=..., impl=..., tk=...)`` is kept as
    a thin shim: it builds (or fetches, via the plan cache) the
    equivalent Transform.  ``lane_width=None`` takes V from the plan's
    autotune/VMEM-guard resolution instead of a hard-coded default.

    Distributed matching: hand the engine a mesh plan (``repro.plan(B,
    mesh=...).engine()``, or ``mesh=``/``axis=`` in the shim form) and
    every correlation batch executes on the plan's lane-packed SHARDED
    inverse -- the outer-product coefficient stacks of the template bank
    are cluster-sharded over the mesh and V templates ride each sharded
    launch (one all-to-all per chunk), so a bank match runs the paper's
    exclusive-memory-range decomposition end to end.  Bank matching
    inherits the plan's resolved ``overlap`` mode with it: on mesh plans
    (``Schedule.overlap == "pipelined"`` by default) a multi-chunk bank
    runs through the executor's double-buffered pipeline, template chunk
    i's iDWT kernel overlapping chunk i-1's all-to-all.
    """

    def __init__(self, B: int | None = None, *, transform=None,
                 dtype=jnp.float64, lane_width: int | None = None,
                 impl: str = "fused", tk: int | None = None, interpret=None,
                 mesh=None, axis=("data", "model")):
        if transform is None:
            if B is None:
                raise ValueError("CorrelationEngine needs B or transform")
            if lane_width is not None and lane_width < 1:
                raise ValueError(
                    f"lane_width must be >= 1, got {lane_width}")
            from repro import plan as plan_mod
            transform = plan_mod.plan(
                B, dtype=dtype, impl=impl,
                V="auto" if lane_width is None else lane_width,
                tk=tk, interpret=interpret, mesh=mesh, axis=axis)
        elif B is not None and B != transform.B:
            raise ValueError(f"B={B} conflicts with transform.B="
                             f"{transform.B}")
        self.transform = transform
        self.B = transform.B
        self.lane_width = transform.V
        self.impl = transform.impl
        self.plan = transform.soft_plan        # compat alias
        self._cdtype = transform.cdtype
        self._mask = jnp.asarray(soft.coeff_mask(self.B))
        self._bank_fn = None
        self.reset_stats()

    def reset_stats(self) -> None:
        """Zero the launch/transform counters (e.g. after compile warmup)."""
        self.stats = dict(launches=0, transforms=0, padded_lanes=0)

    # -- input normalization ------------------------------------------------

    def as_coeffs(self, x) -> jnp.ndarray:
        """Accept S^2 coefficients (B, 2B-1) or grid samples (2B, 2B)."""
        x = jnp.asarray(x)
        B = self.B
        if x.shape == (2 * B, 2 * B):
            x = s2.s2_analysis(x, B)
        if x.shape != (B, 2 * B - 1):
            raise ValueError(
                f"expected S^2 coefficients ({B}, {2 * B - 1}) or samples "
                f"({2 * B}, {2 * B}), got {x.shape}")
        return x.astype(self._cdtype)

    # -- correlation grids --------------------------------------------------

    def _pair_coeffs(self, f, g) -> jnp.ndarray:
        """T[l, m, m'] = conj(f[l, m]) g[l, m'] on the valid-(l,m,m') mask."""
        T = jnp.conj(f)[:, :, None] * g[:, None, :]
        return jnp.where(self._mask, T, 0.0)

    def correlation_grids(self, fs, gs) -> np.ndarray:
        """(N, B, 2B-1) x (N, B, 2B-1) coeff stacks -> (N, 2B, 2B, 2B)
        correlation grids C_n(R) = <f_n, Lambda(R) g_n>.

        Chunks of ``lane_width`` requests run as ONE lane-packed iFSOFT
        launch via the plan's ``inverse_batch`` executor; the final
        partial chunk is zero-padded to the lane width so every launch
        reuses the single compiled kernel shape.  On a mesh plan each
        chunk is one lane-packed SHARDED launch (coefficient stacks
        cluster-sharded, one all-to-all for all V lanes).  Launch
        accounting lands in THIS engine's ``stats`` (the plan is shared;
        its counters are not ours).

        Four spans split the call: ``correlate.pair`` (the pair
        coefficients, dispatched eagerly), ``correlate.dispatch`` (the
        enqueue of the inverse), ``correlate.wait`` (the host blocked on
        the device) and ``correlate.readback`` (the device-to-host copy
        and the conjugate); ``correlate.readback_bytes`` observes the
        bytes copied.
        """
        B = self.B
        if not len(fs):
            return np.zeros((0, 2 * B, 2 * B, 2 * B), complex)
        tags = dict(B=B, lanes=len(fs))
        with obs.span("correlate.pair", **tags):
            T = jnp.stack([self._pair_coeffs(f, g) for f, g in zip(fs, gs)])
        with obs.span("correlate.dispatch", **tags):
            Cb = self.transform.inverse_batch(T, stats=self.stats)
        with obs.span("correlate.wait", **tags):
            Cb = jax.block_until_ready(Cb)
        with obs.span("correlate.readback", **tags):
            C = np.conj(np.asarray(Cb))
        obs.observe("correlate.readback_bytes", Cb.nbytes)
        return C

    # -- matching entry points ----------------------------------------------

    def match(self, f, g, *, refine: bool = True) -> MatchResult:
        """Rotation maximizing <f, Lambda(R) g> for one pair."""
        return self.match_batch([f], [g], refine=refine)[0]

    def match_batch(self, fs, gs, *, refine: bool = True) -> list[MatchResult]:
        """Many independent (f_n, g_n) pairs -> one MatchResult each,
        scored by normalized cross-correlation."""
        fs = [self.as_coeffs(f) for f in fs]
        gs = [self.as_coeffs(g) for g in gs]
        if len(fs) != len(gs):
            raise ValueError(f"got {len(fs)} queries vs {len(gs)} templates")
        C = self.correlation_grids(fs, gs)
        return [peak_euler(C[n], self.B, refine=refine,
                           norm=pair_norm(fs[n], gs[n]))
                for n in range(C.shape[0])]

    # -- a query against a device-resident bank ----------------------------

    def load_bank(self, bank) -> TemplateBank:
        """Upload a template bank once, for any number of queries.

        ``bank``: a list of S^2 coefficient vectors or samples (as
        :meth:`as_coeffs` takes them), a stacked (M, B, 2B-1) array, or a
        :class:`TemplateBank` of this engine (returned as it is)."""
        B, V = self.B, self.lane_width
        if isinstance(bank, TemplateBank):
            if bank.coeffs.shape[1:] != (V, B, 2 * B - 1):
                raise ValueError(
                    f"bank of {bank.coeffs.shape[1:]} chunks does not fit "
                    f"this engine's (V, B, 2B-1) = {(V, B, 2 * B - 1)}")
            return bank
        if not len(bank):
            raise ValueError("empty template bank")
        if tuple(getattr(bank, "shape", ()))[1:] == (B, 2 * B - 1):
            G = jnp.asarray(bank).astype(self._cdtype)
        else:
            G = jnp.stack([self.as_coeffs(g) for g in bank])
        M = G.shape[0]
        norms = np.asarray(jnp.linalg.norm(G.reshape(M, -1), axis=1),
                           np.float64)
        chunks = -(-M // V)
        G = jnp.concatenate(
            [G, jnp.zeros((chunks * V - M,) + G.shape[1:], G.dtype)])
        return TemplateBank(coeffs=G.reshape(chunks, V, B, 2 * B - 1),
                            norms=norms)

    def _bank_chunk(self, f, g):
        """One chunk of V templates g (V, B, 2B-1), all on the device: pair
        coefficients, the plan's lane-packed inverse, the peak search on
        Re C and a gather of the peaks' neighbours -> (flat argmax (V,),
        stencil (V, 7) in the order of :data:`STENCIL`).  Re C = Re of the
        inverse, so no conjugate is taken."""
        V, n = self.lane_width, 2 * self.B
        T = jax.vmap(self._pair_coeffs, in_axes=(None, 0))(f, g)
        re = jnp.real(self.transform.inverse_lanes(T)).reshape(V, -1)
        top, flat = peaks.grid_peaks(re, interpret=self.transform.interpret)
        ii, jj, kk = stencil_ijk(flat // (n * n), flat // n % n, flat % n,
                                 n, jnp)
        near = jnp.take_along_axis(re, ((ii * n + jj) * n + kk)[:, 1:],
                                   axis=1)
        return flat, jnp.concatenate([top[:, None], near], axis=1)

    def _bank_query(self, f, coeffs):
        """A whole query in one executable: :meth:`_bank_chunk` over the
        bank's chunks in a device loop -> (index (chunks*V,), stencil
        (chunks*V, 7), ||f||).  A mesh plan unrolls the chunks into the
        executable instead: XLA's CPU backend gives an FFT of the sharded
        inverse a layout it cannot run inside a device loop."""
        if self.transform.mesh is None:
            index, stencil = jax.lax.map(lambda g: self._bank_chunk(f, g),
                                         coeffs)
        else:
            index, stencil = map(jnp.stack, zip(*[self._bank_chunk(f, g)
                                                  for g in coeffs]))
        return (index.reshape(-1), stencil.reshape(-1, len(STENCIL)),
                jnp.linalg.norm(f))

    def _bank_chain(self):
        """The jitted query, compiled on the first query of each bank
        size."""
        if self._bank_fn is None:
            t = self.transform
            # the plan's kernel operands are built here, outside the trace
            # (built inside it they would be tracers cached on the plan)
            if t.mesh is None:
                t.idwt_fn_batch
            else:
                t.executor()
            self._bank_fn = jax.jit(self._bank_query)
        return self._bank_fn

    def match_bank(self, f, bank, *, refine: bool = True
                   ) -> tuple[int, list[MatchResult]]:
        """One query f against a template bank -> (best index, per-template
        results).  The winner is picked by the normalized score
        (peak / (||f|| ||g||)), so templates of different power compete
        fairly -- a loud template cannot buy its raw peak a win.

        ``bank`` is a :class:`TemplateBank` from :meth:`load_bank` (kept on
        the device across queries) or anything :meth:`load_bank` takes
        (uploaded for this query alone).  The query is one launch of a
        jitted loop whose every step is one V-lane chunk of templates
        (:meth:`_bank_chunk`, counted in ``stats`` as a launch); no grid
        leaves the device, and one readback brings the peaks and their
        stencils home for :func:`refine_stencils`.

        Spans, inside one ``correlate.bank`` (tagged B, templates,
        chunks): ``correlate.dispatch`` (the host's enqueue of the query,
        one ``executor.chunk``), ``correlate.wait`` (the device's work),
        ``correlate.readback`` and ``correlate.refine`` (host refinement
        and ranking); ``correlate.readback_bytes`` observes the bytes read
        back.
        """
        bank = self.load_bank(bank)
        B, V, M = self.B, self.lane_width, len(bank)
        chunks = bank.coeffs.shape[0]
        chain = self._bank_chain()
        tags = dict(B=B, lanes=M)
        mode = "local" if self.transform.mesh is None else "mesh"
        with obs.span("correlate.bank", B=B, templates=M, chunks=chunks):
            f = self.as_coeffs(f)
            with obs.span("correlate.dispatch", **tags):
                with obs.span("executor.chunk", mode=mode,
                              direction="inverse", chunks=chunks, lanes=M):
                    out = chain(f, bank.coeffs)
                self.stats["launches"] += chunks
                self.stats["transforms"] += M
                self.stats["padded_lanes"] += chunks * V - M
            with obs.span("correlate.wait", **tags):
                out = jax.block_until_ready(out)
            with obs.span("correlate.readback", **tags):
                index, stencil, fnorm = jax.device_get(out)
            obs.observe("correlate.readback_bytes",
                        index.nbytes + stencil.nbytes + fnorm.nbytes)
            with obs.span("correlate.refine", B=B, templates=M):
                ijk = np.stack(np.unravel_index(index[:M], (2 * B,) * 3),
                               axis=1)
                results = refine_stencils(B, ijk, stencil[:M],
                                          float(fnorm) * bank.norms, refine)
                best = int(np.argmax([r.rank_key for r in results]))
        return best, results


def correlate(f, g, B: int, *, refine: bool = True, **engine_kw) -> MatchResult:
    """One-shot convenience wrapper: build an engine, match one pair."""
    return CorrelationEngine(B, **engine_kw).match(f, g, refine=refine)
