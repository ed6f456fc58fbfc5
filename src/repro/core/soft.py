"""Sequential reference FSOFT / iFSOFT (Kostelec & Rockmore; paper Sec. 2).

These are the correctness oracles for everything else in the framework:

  * :func:`direct_inverse` / :func:`direct_forward` -- the O(B^6) literal
    triple sums (Eqs. 4/5), tiny B only.
  * :func:`inverse_soft` / :func:`forward_soft` -- the O(B^4)
    separation-of-variables algorithm with a dense Wigner table:
    2-D FFT over (alpha, gamma) + per-(m, m') DWT (Sec. 2.4).

Coefficient layout ("dense"): complex array fhat[l, m + B - 1, m' + B - 1]
of shape (B, 2B-1, 2B-1); entries with l < max(|m|, |m'|) are zero.
Sample layout: complex array f[i, j, k] on the (alpha_i, beta_j, gamma_k)
grid of shape (2B, 2B, 2B).

Everything here is numpy in complex128 on the host, whatever jax's x64
flag says: the chip path runs f32 with x64 off and is checked against
these f64 results.
"""
from __future__ import annotations

import numpy as np

from . import quadrature, wigner

__all__ = [
    "coeff_count", "random_coeffs", "coeff_mask",
    "s2_coeff_mask", "random_s2_coeffs",
    "direct_inverse", "direct_forward",
    "inverse_soft", "forward_soft",
]


def coeff_count(B: int) -> int:
    """Number of potentially nonzero coefficients: B (4B^2 - 1) / 3."""
    return B * (4 * B * B - 1) // 3


def coeff_mask(B: int) -> np.ndarray:
    """Boolean mask of valid (l, m, m') cells in the dense layout."""
    l = np.arange(B)[:, None, None]
    m = np.abs(np.arange(-(B - 1), B))[None, :, None]
    mp = np.abs(np.arange(-(B - 1), B))[None, None, :]
    return (m <= l) & (mp <= l)


def random_coeffs(B: int, seed: int = 0, dtype=np.complex128) -> np.ndarray:
    """Random coefficients as in the paper's benchmark: Re, Im ~ U[-1, 1]."""
    rng = np.random.default_rng(seed)
    f = (rng.uniform(-1, 1, (B, 2 * B - 1, 2 * B - 1))
         + 1j * rng.uniform(-1, 1, (B, 2 * B - 1, 2 * B - 1)))
    return (f * coeff_mask(B)).astype(dtype)


def s2_coeff_mask(B: int) -> np.ndarray:
    """Boolean mask of valid (l, m) cells in the dense S^2 layout (B, 2B-1)."""
    l = np.arange(B)[:, None]
    m = np.abs(np.arange(-(B - 1), B))[None, :]
    return m <= l


def random_s2_coeffs(B: int, seed: int = 0, dtype=np.complex128) -> np.ndarray:
    """Seeded random S^2 coefficients flm[l, m + B - 1], |m| <= l < B.

    The single source of bandlimited spherical test signals shared by
    examples, benchmarks, and the :mod:`repro.so3` tests (Re, Im ~ N(0, 1)
    on the valid cells, zero elsewhere).
    """
    rng = np.random.default_rng(seed)
    f = (rng.normal(size=(B, 2 * B - 1))
         + 1j * rng.normal(size=(B, 2 * B - 1)))
    return (f * s2_coeff_mask(B)).astype(dtype)


# ---------------------------------------------------------------------------
# O(B^6) direct transforms (tiny-B oracle)
# ---------------------------------------------------------------------------

def _wigner_D(B: int):
    """D(l,m,m'; a_i, b_j, g_k) = e^{-im a} d(l,m,m'; b) e^{-im' g}."""
    a = quadrature.alphas(B)
    b = quadrature.betas(B)
    d = wigner.wigner_d_table(B, b)  # (B, 2B-1, 2B-1, 2B)
    mm = np.arange(-(B - 1), B)
    ea = np.exp(-1j * np.outer(mm, a))  # (2B-1, 2B)
    return d, ea


def direct_inverse(fhat: np.ndarray) -> np.ndarray:
    """f(a_i, b_j, g_k) = sum_{l,m,m'} fhat D(l,m,m')  -- O(B^6)."""
    B = fhat.shape[0]
    d, ea = _wigner_D(B)
    # g[m, j, m'] = sum_l fhat[l,m,m'] d[l,m,m',j]
    g = np.einsum("lmp,lmpj->mjp", np.asarray(fhat), d)
    return np.einsum("mi,mjp,pk->ijk", ea, g, ea)


def direct_forward(f: np.ndarray, B: int) -> np.ndarray:
    """fhat(l,m,m') = (2l+1)/(8piB) sum_{ijk} w(j) f conj(D)  -- O(B^6)."""
    d, ea = _wigner_D(B)
    w = quadrature.weights(B)
    # S[m, j, m'] = sum_{i,k} f[i,j,k] e^{+im a_i} e^{+im' g_k}
    S = np.einsum("mi,ijk,pk->mjp", np.conj(ea), np.asarray(f), np.conj(ea))
    scale = (2 * np.arange(B) + 1) / (8 * np.pi * B)
    out = np.einsum("lmpj,j,mjp->lmp", d, w, S)
    return scale[:, None, None] * out * coeff_mask(B)


# ---------------------------------------------------------------------------
# O(B^4) separated transforms (dense Wigner table)
# ---------------------------------------------------------------------------

def _bin_index(B: int) -> np.ndarray:
    """FFT bin of each order m = -(B-1)..(B-1): m mod 2B."""
    return np.arange(-(B - 1), B) % (2 * B)


def inverse_soft(fhat, d_table=None):
    """iFSOFT: coefficients (B, 2B-1, 2B-1) -> samples (2B, 2B, 2B).

    iDWT (g = sum_l fhat d) followed by an unnormalized forward 2-D FFT
    over the m -> i and m' -> k axes.
    """
    fhat = np.asarray(fhat, np.complex128)
    B = fhat.shape[0]
    if d_table is None:
        d_table = wigner.wigner_d_table(B)
    d = np.asarray(d_table, np.float64)
    g = np.einsum("lmp,lmpj->mjp", fhat, d, optimize=True)
    bins = _bin_index(B)
    gbin = np.zeros((2 * B, 2 * B, 2 * B), np.complex128)
    gbin[np.ix_(bins, np.arange(2 * B), bins)] = g
    return np.fft.fft(np.fft.fft(gbin, axis=0), axis=2)


def forward_soft(f, B: int, d_table=None):
    """FSOFT: samples (2B, 2B, 2B) -> coefficients (B, 2B-1, 2B-1).

    Unnormalized inverse 2-D FFT (positive exponent) to get S(m, m'; j),
    then the weighted DWT per (m, m') (paper Eq. 5).
    """
    if d_table is None:
        d_table = wigner.wigner_d_table(B)
    d = np.asarray(d_table, np.float64)
    f = np.asarray(f, np.complex128)
    S = (2 * B) ** 2 * np.fft.ifft(np.fft.ifft(f, axis=0), axis=2)
    bins = _bin_index(B)
    Ssel = S[np.ix_(bins, np.arange(2 * B), bins)]  # (2B-1, 2B, 2B-1)
    w = quadrature.weights(B)
    scale = (2 * np.arange(B) + 1) / (8 * np.pi * B)
    out = np.einsum("lmpj,j,mjp->lmp", d, w, Ssel, optimize=True)
    return scale[:, None, None] * out * coeff_mask(B)
