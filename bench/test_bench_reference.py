"""The plain f64 reference and the traffic generator."""
import numpy as np
import pytest
from scipy.special import eval_jacobi, gammaln

from bench import reference as ref
from bench import traffic


def _d_explicit(l, m, mp, beta):
    """d(l, m, m'; beta) from the Jacobi-polynomial formula, for m' >= |m|,
    other pairs by d(l, m, m') = (-1)^(m-m') d(l, m', m) and d(l, -m, -m')."""
    if mp < abs(m):
        if m > mp:
            return (-1.0) ** (m - mp) * _d_explicit(l, mp, m, beta)
        return (-1.0) ** (m - mp) * _d_explicit(l, -m, -mp, beta)
    c = (-1.0) ** (mp - m) * np.exp(0.5 * (
        gammaln(l + mp + 1) - gammaln(l + m + 1)
        + gammaln(l - mp + 1) - gammaln(l - m + 1)))
    return (c * np.sin(beta / 2) ** (mp - m) * np.cos(beta / 2) ** (m + mp)
            * eval_jacobi(l - mp, mp - m, m + mp, np.cos(beta)))


def test_d_rows_and_symmetries_match_the_explicit_formula():
    B = 7
    beta = ref.betas(B)
    m, mp = ref.fundamental_pairs(B)
    rows = ref.d_rows(B, m, mp, beta)
    worst = 0.0
    for a, b, sign, reflected in ref._members(B, m, mp):
        for p in range(len(m)):
            for l in range(max(abs(a[p]), abs(b[p])), B):
                got = sign[p, l] * (rows[p, l, ::-1] if reflected
                                    else rows[p, l])
                want = _d_explicit(l, int(a[p]), int(b[p]), beta)
                worst = max(worst, np.abs(got - want).max())
    assert worst < 1e-12


@pytest.mark.parametrize("B", [4, 8, 16])
def test_roundtrip_is_exact_in_f64(B):
    r = np.random.default_rng(B)
    mask = ref.coeff_mask(B)
    fhat = (r.uniform(-1, 1, mask.shape)
            + 1j * r.uniform(-1, 1, mask.shape)) * mask
    grid, back = ref.inverse_and_forward(fhat, ref.inverse(fhat))
    assert np.abs(back - fhat).max() < 1e-12
    assert np.abs(grid - ref.inverse(fhat)).max() == 0


def test_agrees_with_the_programs_own_f64_transforms():
    from repro.core import soft
    from repro.so3 import s2

    B = 8
    fhat = soft.random_coeffs(B, 3)
    f = soft.inverse_soft(fhat)
    assert np.abs(ref.inverse(fhat) - f).max() < 1e-12 * np.abs(f).max()
    assert np.abs(ref.forward(f) - soft.forward_soft(f, B)).max() < 1e-12
    g = soft.random_s2_coeffs(B, 5)
    euler = (0.7, 1.1, 2.9)
    assert np.abs(ref.rotate_s2(g, euler)
                  - s2.rotate_s2_coeffs(g, euler)).max() < 1e-12


def test_correlation_peaks_at_the_hidden_rotation():
    B = 8
    r = np.random.default_rng(0)
    g = (r.normal(size=(B, 2 * B - 1)) + 1j * r.normal(size=(B, 2 * B - 1)))
    g *= np.abs(np.arange(-(B - 1), B))[None, :] <= np.arange(B)[:, None]
    i, j, k = 3, 5, 11                 # a grid point, so the peak is exact
    euler = (i * np.pi / B, ref.betas(B)[j], k * np.pi / B)
    C = ref.correlation(ref.rotate_s2(g, euler), g)
    assert np.unravel_index(np.argmax(C.real), C.shape) == (i, j, k)


def test_bf16_control_is_far_from_f64():
    B = 8
    fhat = ref.coeff_mask(B) * (1 + 0.5j)
    exact = ref.inverse(fhat)
    low = ref.inverse(fhat, ref.bf16_round)
    assert 1e-4 < np.abs(low - exact).max() / np.abs(exact).max() < 0.1


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40, -3])
def test_open_loop_offers_the_same_load_for_every_seed(seed):
    mix = {"rate_per_s": 10.0, "pool": 4}
    due, idx = traffic.open_schedule(mix, seed, 30.0)
    base_due, base_idx = traffic.open_schedule(mix, 1, 30.0)
    assert len(due) == 300 and 0 < due[0] and due[-1] < 30.0
    assert np.allclose(np.sort(np.diff(due, prepend=0)),
                       np.sort(np.diff(base_due, prepend=0)))
    assert np.array_equal(np.bincount(idx), np.bincount(base_idx))
    again, _ = traffic.open_schedule(mix, seed, 30.0)
    assert np.array_equal(due, again)


def test_arrival_seed_fixes_the_arrivals_and_the_run_seed_the_pool():
    mix = {"rate_per_s": 10.0, "pool": 4, "arrival_seed": 11}
    due, idx = traffic.open_schedule(mix, 3, 30.0)
    other_due, other_idx = traffic.open_schedule(mix, 2 ** 33 + 1, 30.0)
    assert np.array_equal(due, other_due)
    assert not np.array_equal(idx, other_idx)
    assert np.array_equal(np.bincount(idx), np.bincount(other_idx))
