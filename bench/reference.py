"""Plain f64 reference of the SO(3) FFT and of S^2 rotational correlation.

Written from the mathematics alone (Kostelec & Rockmore, "FFTs on the
rotation group", JFAA 14 (2008); Lux, Wuelker & Chirikjian,
arXiv:1808.00896, Sec. 2); it imports nothing of the system under test.

Conventions (the ones the transform is defined by):

  coefficients  fhat[l, m + B - 1, m' + B - 1], shape (B, 2B-1, 2B-1),
                zero where l < max(|m|, |m'|)
  samples       f[i, j, k] at alpha_i = i pi / B, beta_j = (2j+1) pi / (4B),
                gamma_k = k pi / B, shape (2B, 2B, 2B)
  inverse       f = sum_{l,m,m'} fhat e^{-i m alpha} d(l,m,m'; beta)
                    e^{-i m' gamma}
  forward       fhat = (2l+1)/(8 pi B) sum_{ijk} w_j f e^{+i m alpha}
                       d(l,m,m'; beta) e^{+i m' gamma}

The Wigner d-rows come from the three-term recurrence in l, seeded at
l = m in the log domain, on the fundamental domain 0 <= m' <= m; the seven
symmetries give every other order pair.  The contraction runs in blocks of
fundamental pairs, so the dense (B, 2B-1, 2B-1, 2B) table never exists:
at B = 128 it would take 17 GB.

``rnd`` rounds the recurrence state, the d-rows and the operands after
every step; ``bf16_round`` makes the whole computation bfloat16, which is
the lower-precision control of the benchmark's comparisons.
"""
from __future__ import annotations

import numpy as np
from scipy.special import gammaln

PAIR_BLOCK = 512


def betas(B: int) -> np.ndarray:
    return (2 * np.arange(2 * B) + 1) * np.pi / (4 * B)


def weights(B: int) -> np.ndarray:
    """Quadrature weights w_B(j) of the 2B-point beta grid."""
    b = betas(B)
    i = np.arange(B, dtype=np.float64)[:, None]
    ser = np.sum(np.sin((2 * i + 1) * b[None, :]) / (2 * i + 1), axis=0)
    return (2 * np.pi / B ** 2) * np.sin(b) * ser


def coeff_mask(B: int) -> np.ndarray:
    l = np.arange(B)[:, None, None]
    m = np.abs(np.arange(-(B - 1), B))
    return (m[None, :, None] <= l) & (m[None, None, :] <= l)


def coeff_count(B: int) -> int:
    """Valid coefficients of a bandwidth-B function: B (4B^2 - 1) / 3."""
    return B * (4 * B * B - 1) // 3


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back (complex parts separately)."""
    import ml_dtypes

    if np.iscomplexobj(x):
        return bf16_round(x.real) + 1j * bf16_round(x.imag)
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def _identity(x):
    return x


def fundamental_pairs(B: int) -> tuple[np.ndarray, np.ndarray]:
    m, mp = np.tril_indices(B)          # 0 <= m' <= m < B
    return m, mp


def d_rows(B: int, m: np.ndarray, mp: np.ndarray, beta: np.ndarray,
           rnd=_identity) -> np.ndarray:
    """d(l, m_p, m'_p; beta_j) for 0 <= m'_p <= m_p, all l < B: (P, B, J)."""
    m = np.asarray(m, np.float64)[:, None]
    mp = np.asarray(mp, np.float64)[:, None]
    beta = np.asarray(beta, np.float64)[None, :]
    with np.errstate(divide="ignore"):
        log_seed = (0.5 * (gammaln(2 * m + 1) - gammaln(m + mp + 1)
                           - gammaln(m - mp + 1))
                    + (m + mp) * np.log(np.cos(beta / 2))
                    + (m - mp) * np.log(np.sin(beta / 2)))
    seed = rnd(np.exp(log_seed))
    cb = np.cos(beta)
    P, J = m.shape[0], beta.shape[1]
    out = np.zeros((P, B, J))
    prev = np.zeros((P, J))
    cur = np.zeros((P, J))
    for l in range(B):
        # rows before their start stay 0: the recurrence maps (0, 0) to 0
        start = m[:, 0] == l
        cur[start] = seed[start]
        prev[start] = 0.0
        out[:, l] = cur
        if l == B - 1:
            break
        lp1 = l + 1.0
        den = np.sqrt(np.maximum((lp1 ** 2 - m ** 2) * (lp1 ** 2 - mp ** 2),
                                 1.0))
        a = lp1 * (2 * l + 1) / den
        if l > 0:
            mu = m * mp / (l * lp1)
            c = lp1 * np.sqrt(np.maximum((l ** 2 - m ** 2) * (l ** 2 - mp ** 2),
                                         0.0)) / (l * den)
        else:
            mu = c = np.zeros_like(m)
        prev, cur = cur, rnd(rnd(a * (cb - mu)) * cur - rnd(c * prev))
    return out


def _members(B: int, m: np.ndarray, mp: np.ndarray):
    """The eight order pairs each fundamental pair stands for.

    Yields (a, b, sign[P, B], reflected): d(l, a, b; beta) equals
    sign * d(l, m, m'; beta), or sign * d(l, m, m'; pi - beta) where
    ``reflected``.
    """
    l = np.arange(B)[None, :]
    par_l = (-1.0) ** l
    s_swap = ((-1.0) ** (m - mp))[:, None] * np.ones((1, B))
    one = np.ones((len(m), B))
    lm = par_l * ((-1.0) ** m)[:, None]
    lmp = par_l * ((-1.0) ** mp)[:, None]
    yield m, mp, one, False
    yield mp, m, s_swap, False
    yield -m, -mp, s_swap, False
    yield -mp, -m, one, False
    yield -m, mp, lmp, True
    yield -mp, m, lmp, True
    yield m, -mp, lm, True
    yield mp, -m, lm, True


def _blocks(B: int):
    m, mp = fundamental_pairs(B)
    for p0 in range(0, len(m), PAIR_BLOCK):
        yield m[p0:p0 + PAIR_BLOCK], mp[p0:p0 + PAIR_BLOCK]


def _contract(B: int, fhat=None, S=None, rnd=_identity):
    """The Wigner contractions of both directions, sharing the d-rows.

    fhat (B, 2B-1, 2B-1) -> g[m, j, m'] = sum_l fhat[l, m, m'] d(l, m, m';
    beta_j); S (2B-1, 2B, 2B-1) -> (2l+1)/(8 pi B) sum_j w_j d(l, m, m';
    beta_j) S[m, j, m'].  Either may be None; returns (g, fhat_out).
    """
    J = 2 * B
    beta = betas(B)
    w = weights(B)
    g = out = None
    if fhat is not None:
        fhat = rnd(np.asarray(fhat, np.complex128))
        g = np.zeros((2 * B - 1, J, 2 * B - 1), np.complex128)
    if S is not None:
        S = rnd(np.asarray(S, np.complex128))
        out = np.zeros((B, 2 * B - 1, 2 * B - 1), np.complex128)
    for m, mp in _blocks(B):
        rows = d_rows(B, m, mp, beta, rnd)                     # (P, B, J)
        members = list(_members(B, m, mp))
        if fhat is not None:
            c = np.stack([fhat[:, a + B - 1, b + B - 1].T * sign
                          for a, b, sign, _ in members], axis=1)  # (P, 8, B)
            res = np.matmul(np.concatenate([c.real, c.imag], axis=1), rows)
            res = rnd(res[:, :8] + 1j * res[:, 8:])            # (P, 8, J)
            for k, (a, b, _, reflected) in enumerate(members):
                r = res[:, k, ::-1] if reflected else res[:, k]
                g[a + B - 1, :, b + B - 1] = r
        if S is not None:
            v = np.stack([S[a + B - 1, ::-1 if reflected else 1,
                            b + B - 1] * (w[::-1] if reflected else w)
                          for a, b, _, reflected in members], axis=2)
            res = np.matmul(rows, np.concatenate([v.real, v.imag], axis=2))
            res = res[..., :8] + 1j * res[..., 8:]             # (P, B, 8)
            for k, (a, b, sign, _) in enumerate(members):
                out[:, a + B - 1, b + B - 1] = rnd(res[:, :, k] * sign).T
    if out is not None:
        scale = (2 * np.arange(B) + 1) / (8 * np.pi * B)
        out = out * scale[:, None, None] * coeff_mask(B)
    return g, out


def _bins(B: int) -> np.ndarray:
    return np.arange(-(B - 1), B) % (2 * B)


def _synthesis(g: np.ndarray, rnd=_identity) -> np.ndarray:
    B = (g.shape[0] + 1) // 2
    grid = np.zeros((2 * B, 2 * B, 2 * B), np.complex128)
    grid[np.ix_(_bins(B), np.arange(2 * B), _bins(B))] = g
    grid = rnd(np.fft.fft(grid, axis=0))
    return rnd(np.fft.fft(grid, axis=2))


def _analysis(f: np.ndarray, rnd=_identity) -> np.ndarray:
    B = f.shape[0] // 2
    S = rnd(np.fft.ifft(np.asarray(f, np.complex128), axis=0))
    S = rnd(np.fft.ifft(S, axis=2) * (2 * B) ** 2)
    return S[np.ix_(_bins(B), np.arange(2 * B), _bins(B))]


def inverse(fhat: np.ndarray, rnd=_identity) -> np.ndarray:
    """iFSOFT: coefficients (B, 2B-1, 2B-1) -> samples (2B, 2B, 2B)."""
    return _synthesis(_contract(fhat.shape[0], fhat=fhat, rnd=rnd)[0], rnd)


def forward(f: np.ndarray, rnd=_identity) -> np.ndarray:
    """FSOFT: samples (2B, 2B, 2B) -> coefficients (B, 2B-1, 2B-1)."""
    return _contract(f.shape[0] // 2, S=_analysis(f, rnd), rnd=rnd)[1]


def inverse_and_forward(fhat: np.ndarray, f: np.ndarray):
    """(inverse(fhat), forward(f)) in one pass over the d-rows."""
    B = fhat.shape[0]
    g, out = _contract(B, fhat=fhat, S=_analysis(f))
    return _synthesis(g), out


def wigner_D(B: int, euler) -> np.ndarray:
    """D^l_{m m'}(alpha, beta, gamma) = e^{-i m alpha} d(l, m, m'; beta)
    e^{-i m' gamma}: (B, 2B-1, 2B-1), zero where |m| or |m'| > l."""
    alpha, beta, gamma = euler
    m, mp = fundamental_pairs(B)
    rows = d_rows(B, m, mp, np.array([beta, np.pi - beta]))   # (P, B, 2)
    d = np.zeros((B, 2 * B - 1, 2 * B - 1))
    for a, b, sign, reflected in _members(B, m, mp):
        d[:, a + B - 1, b + B - 1] = (rows[:, :, 1 if reflected else 0]
                                      * sign).T
    k = np.arange(-(B - 1), B)
    return (np.exp(-1j * k * alpha)[None, :, None] * d
            * np.exp(-1j * k * gamma)[None, None, :])


def rotate_s2(flm: np.ndarray, euler) -> np.ndarray:
    """(Lambda(R) f)_{lm} = sum_{m'} D^l_{m m'}(R) f_{lm'}."""
    return np.einsum("lmp,lp->lm", wigner_D(flm.shape[0], euler), flm)


def correlation(f: np.ndarray, g: np.ndarray, rnd=_identity) -> np.ndarray:
    """C(R) = <f, Lambda(R) g> on the (2B)^3 Euler grid, for S^2
    coefficients f, g of shape (B, 2B-1)."""
    B = f.shape[0]
    T = np.conj(f)[:, :, None] * g[:, None, :] * coeff_mask(B)
    return np.conj(inverse(T, rnd))
