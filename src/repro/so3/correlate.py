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

from . import s2

__all__ = ["MatchResult", "CorrelationEngine", "correlate", "angle_error",
           "random_rotation", "result_key"]


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
    the norms were unavailable or zero)."""

    alpha: float
    beta: float
    gamma: float
    peak: float
    index: tuple[int, int, int]
    score: float | None = None

    @property
    def euler(self) -> tuple[float, float, float]:
        return (self.alpha, self.beta, self.gamma)

    @property
    def rank_key(self) -> float:
        """Cross-template ranking value: the normalized score when
        available, else the raw peak."""
        return self.peak if self.score is None else self.score


def _parabolic_offset(ym: float, y0: float, yp: float) -> float:
    """Sub-grid offset of a quadratic through three equispaced samples,
    clamped to half a grid step (0 when the stencil is degenerate)."""
    den = ym - 2.0 * y0 + yp
    if den == 0.0 or not np.isfinite(den):
        return 0.0
    return float(np.clip(0.5 * (ym - yp) / den, -0.5, 0.5))


def peak_euler(C: np.ndarray, B: int, refine: bool = True,
               norm: float | None = None) -> MatchResult:
    """Argmax of Re C over the (2B)^3 Euler grid -> MatchResult.

    refine=True fits a 1-D quadratic per axis through the peak (periodic
    wrap on alpha/gamma; beta skips refinement at the grid edges), pushing
    the error below the pi/B grid resolution for well-separated peaks.
    `norm` = ||f|| ||g|| of the correlated pair; when given (and nonzero)
    the result carries score = peak / norm.
    """
    Cr = np.asarray(C).real
    i, j, k = np.unravel_index(int(np.argmax(Cr)), Cr.shape)
    a = float(quadrature.alphas(B)[i])
    b = float(quadrature.betas(B)[j])
    g = float(quadrature.gammas(B)[k])
    if refine:
        n = 2 * B
        step_ag = np.pi / B
        step_b = np.pi / (2 * B)
        a += step_ag * _parabolic_offset(
            Cr[(i - 1) % n, j, k], Cr[i, j, k], Cr[(i + 1) % n, j, k])
        g += step_ag * _parabolic_offset(
            Cr[i, j, (k - 1) % n], Cr[i, j, k], Cr[i, j, (k + 1) % n])
        if 0 < j < n - 1:
            b += step_b * _parabolic_offset(
                Cr[i, j - 1, k], Cr[i, j, k], Cr[i, j + 1, k])
        a %= 2 * np.pi
        g %= 2 * np.pi
    peak = float(Cr[i, j, k])
    score = peak / norm if norm else None
    return MatchResult(alpha=a, beta=b, gamma=g, peak=peak,
                       index=(int(i), int(j), int(k)), score=score)


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

    def match_bank(self, f, bank, *, refine: bool = True
                   ) -> tuple[int, list[MatchResult]]:
        """One query f against a template bank -> (best index, per-template
        results).  The winner is picked by the normalized score
        (peak / (||f|| ||g||)), so templates of different power compete
        fairly -- a loud template cannot buy its raw peak a win."""
        if not len(bank):
            raise ValueError("empty template bank")
        f = self.as_coeffs(f)
        results = self.match_batch([f] * len(bank), list(bank), refine=refine)
        best = int(np.argmax([r.rank_key for r in results]))
        return best, results


def correlate(f, g, B: int, *, refine: bool = True, **engine_kw) -> MatchResult:
    """One-shot convenience wrapper: build an engine, match one pair."""
    return CorrelationEngine(B, **engine_kw).match(f, g, refine=refine)
