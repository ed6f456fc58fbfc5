"""SO(3) correlation engine -- rotational matching served on the fused
iFSOFT stack.

This package is the first *application subsystem* over the transform
core: it turns "find the rotation aligning two spherical signals" into
batched inverse SO(3) FFT launches at production request shapes.

Math (the correlation theorem)
------------------------------
For bandlimited f, g on S^2 with coefficients f[l, m], g[l, m'] in the
basis Ytil_{lm}(alpha, beta) = e^{-i m alpha} d^l_{m0}(beta), and the
rotation action (Lambda(R) g)_{lm} = sum_{m'} D^l_{mm'}(R) g[l, m'], the
correlation over all rotations

    C(R) = sum_l <f_l, D^l(R) g_l>
         = sum_{l, m, m'}  conj(f[l, m]) g[l, m']  D^l_{mm'}(R)

is itself a bandlimited function on SO(3) whose *coefficients* are the
outer products T[l, m, m'] = conj(f[l, m]) g[l, m'].  One inverse SO(3)
FFT of T therefore evaluates C on the whole (2B)^3 Euler grid at once --
O(B^3 log B + B^4) instead of O(B^6) for naive rotation search -- and the
argmax (plus quadratic sub-grid refinement) recovers the aligning
rotation to better than the pi/B grid resolution.  This is the
Kovacs-Wriggers fast rotational matching family (cryo-EM fitting,
docking, shape retrieval) that motivates the iFSOFT (PAPER.md Sec. 1).

Layers
------
  :mod:`repro.so3.s2`         forward/inverse spherical-harmonic transform
                              on the 2B x 2B grid (the m' = 0 Wigner
                              column of the DWT machinery = associated
                              Legendre), so raw S^2 samples enter the
                              pipeline without precomputed coefficients.
  :mod:`repro.so3.correlate`  :class:`CorrelationEngine` -- outer-product
                              coefficient batches through a
                              :class:`repro.plan.Transform`'s lane-packed
                              ``inverse_batch`` executor (the plan
                              resolves the iDWT schedule and lane width
                              V); pair / one-vs-bank / many-vs-many entry
                              points, peak refinement, and normalized
                              cross-correlation scores.  Build from a
                              plan: ``repro.plan(B).engine()``.
  :mod:`repro.so3.service`    :class:`SO3Service` -- micro-batching queue
                              that packs same-bandwidth requests into the
                              V lanes (``lane_width=None`` takes V from
                              each bandwidth's plan), warms plan/kernel
                              caches at startup, and reports
                              latency/throughput.
                              CLI: ``python -m repro.launch.serve_so3``.

Latency/throughput note
-----------------------
One fused launch serves V requests; each on-the-fly Wigner d-row is
generated once and contracted against V*C*2 lanes, so per-request cost
approaches 1/V of a solo launch as lanes fill (benchmarks/run.py
--section correlation measures occupancy and per-request wall time).
Latency-sensitive callers keep ``max_wait_ms`` small (partial lanes are
zero-padded -- the compiled kernel shape never changes); throughput
callers batch via :meth:`CorrelationEngine.match_batch` directly.
"""
from . import correlate, s2, service  # noqa: F401
from .correlate import (CorrelationEngine, MatchResult, angle_error,  # noqa: F401
                        correlate as match_pair, result_key)
from .service import (Cancelled, Expired, Rejected, ServiceError,  # noqa: F401
                      SO3Service)

__all__ = ["s2", "correlate", "service", "CorrelationEngine", "MatchResult",
           "match_pair", "angle_error", "result_key", "SO3Service",
           "ServiceError", "Rejected", "Expired", "Cancelled"]
