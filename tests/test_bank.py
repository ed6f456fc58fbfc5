"""One query against a device-resident template bank: the ``grid_peaks``
kernel in interpret mode, and ``CorrelationEngine.match_bank``'s chunked
device path against the f64 host path (``core/soft`` inverse, then
``peak_euler``)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs, plan
from repro.core import quadrature, soft
from repro.kernels.peaks import LANES, ROWS_PER_BLOCK, grid_peaks
from repro.so3 import CorrelationEngine, MatchResult, result_key, s2
from repro.so3.correlate import (STENCIL, TemplateBank, pair_norm,
                                 peak_euler, random_rotation, stencil_ijk)

B, M, V = 8, 11, 8                 # two chunks, the second with 5 padded lanes


# ---------------------------------------------------------------------------
# grid_peaks
# ---------------------------------------------------------------------------

N = 128                  # (N, N, N) grids of 8 blocks of the kernel's rows


def _check_peaks(x):
    top, idx = grid_peaks(jnp.asarray(x))
    flat = np.asarray(x).reshape(x.shape[0], -1)
    np.testing.assert_array_equal(np.asarray(idx), flat.argmax(axis=1))
    np.testing.assert_array_equal(np.asarray(top), flat.max(axis=1))
    return np.asarray(top), np.asarray(idx)


def test_a_large_grid_spans_several_blocks():
    rows = N ** 3 // LANES
    assert rows // ROWS_PER_BLOCK == 8 and rows % ROWS_PER_BLOCK == 0


@pytest.mark.parametrize("shape,dtype", [
    ((3, 16, 16, 16), np.float32), ((2, 16, 16, 16), np.float64),
    ((2, 8, 8, 8), np.float32), ((1, 5, 7, 3), np.float32),
    ((2, N, N, N), np.float32)])         # the maximum carried across blocks
def test_grid_peaks_matches_argmax_on_random_grids(shape, dtype):
    x = np.random.default_rng(0).normal(size=shape).astype(dtype)
    _check_peaks(x)


@pytest.mark.parametrize("where", [(0, 5, 9), (N - 1, 5, 9),     # alpha ends
                                   (4, 0, 9), (4, N - 1, 9),     # beta ends
                                   (4, 5, 0), (4, 5, N - 1)])    # gamma ends
def test_grid_peaks_finds_peaks_on_the_grid_edges(where):
    x = np.random.default_rng(1).normal(size=(2, N, N, N)).astype(np.float32)
    x[1][where] = 10.0
    _, idx = _check_peaks(x)
    assert np.unravel_index(idx[1], (N,) * 3) == where


def test_grid_peaks_ties_go_to_the_first_index():
    x = np.zeros((3, N, N, N), np.float32)
    x[0][2, 3, 4] = x[0][90, 1, 1] = 1.0           # different blocks
    x[1][0, 0, 5] = x[1][0, 0, 2] = 1.0            # one row of 128
    x[2][0, 1, 0] = x[2][0, 0, 7] = 1.0            # one column, two rows
    _, idx = _check_peaks(x)
    assert idx.tolist() == [np.ravel_multi_index(ix, (N,) * 3) for ix in
                            [(2, 3, 4), (0, 0, 2), (0, 0, 7)]]


def test_grid_peaks_on_a_padded_lane():
    """A zero lane (a padded template) reads peak 0 at index 0, and the
    lanes beside it are untouched."""
    x = np.random.default_rng(2).normal(size=(3, 8, 8, 8))
    x[1] = 0.0
    top, idx = _check_peaks(x)
    assert top[1] == 0.0 and idx[1] == 0


# ---------------------------------------------------------------------------
# the refinement, shared by the host grid path and the bank path
# ---------------------------------------------------------------------------

def _scalar_peak_euler(C, B, refine=True, norm=None):
    """The refinement as one scalar loop per axis (the form peak_euler had
    before it was vectorised over stencils): the oracle of bitwise
    equality."""
    def offset(ym, y0, yp):
        den = ym - 2.0 * y0 + yp
        if den == 0.0 or not np.isfinite(den):
            return 0.0
        return float(np.clip(0.5 * (ym - yp) / den, -0.5, 0.5))

    Cr = np.asarray(C).real
    i, j, k = np.unravel_index(int(np.argmax(Cr)), Cr.shape)
    a = float(quadrature.alphas(B)[i])
    b = float(quadrature.betas(B)[j])
    g = float(quadrature.gammas(B)[k])
    if refine:
        n = 2 * B
        a += np.pi / B * offset(Cr[(i - 1) % n, j, k], Cr[i, j, k],
                                Cr[(i + 1) % n, j, k])
        g += np.pi / B * offset(Cr[i, j, (k - 1) % n], Cr[i, j, k],
                                Cr[i, j, (k + 1) % n])
        if 0 < j < n - 1:
            b += np.pi / (2 * B) * offset(Cr[i, j - 1, k], Cr[i, j, k],
                                          Cr[i, j + 1, k])
        a %= 2 * np.pi
        g %= 2 * np.pi
    peak = float(Cr[i, j, k])
    return MatchResult(alpha=a, beta=b, gamma=g, peak=peak,
                       index=(int(i), int(j), int(k)),
                       score=peak / norm if norm else None)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("where", [None, (0, 3, 15), (15, 0, 4), (7, 15, 0)])
@pytest.mark.parametrize("refine", [True, False])
def test_vectorised_refinement_is_bitwise_the_scalar_one(dtype, where,
                                                         refine):
    C = np.random.default_rng(3).normal(size=(16, 16, 16)).astype(dtype)
    if where is not None:
        C[where] = 5.0
    for norm in (None, 0.0, 7.25):
        got = peak_euler(C, 8, refine=refine, norm=norm)
        assert result_key(got) == result_key(
            _scalar_peak_euler(C, 8, refine=refine, norm=norm))
    # the stencil the refinement read: wrapped alpha/gamma, clamped beta
    i, j, k = got.index
    ii, jj, kk = stencil_ijk(i, j, k, 16)
    assert got.stencil == tuple(float(v) for v in C[ii, jj, kk][1:])
    assert len(STENCIL) == 1 + len(got.stencil)


def test_stencil_wraps_alpha_and_gamma_and_stops_at_beta_edges():
    ii, jj, kk = stencil_ijk(np.array([0, 15]), np.array([0, 15]),
                             np.array([15, 0]), 16)
    assert ii.tolist() == [[0, 15, 1, 0, 0, 0, 0], [15, 14, 0, 15, 15, 15,
                                                    15]]
    assert jj.tolist() == [[0, 0, 0, 0, 1, 0, 0], [15, 15, 15, 14, 15, 15,
                                                    15]]
    assert kk.tolist() == [[15, 15, 15, 15, 15, 14, 0], [0, 0, 0, 0, 0, 15,
                                                         1]]


# ---------------------------------------------------------------------------
# match_bank on the device path
# ---------------------------------------------------------------------------

def _bank(seed=20):
    return [soft.random_s2_coeffs(B, seed=seed + i) for i in range(M)]


def _query(bank, planted=6):
    return s2.rotate_s2_coeffs(bank[planted], random_rotation(4))


def _host_results(query, bank):
    """The f64 host path: each correlation grid by the dense inverse of
    core/soft, then peak_euler on it."""
    out = []
    for g in bank:
        T = np.conj(query)[:, :, None] * np.asarray(g)[:, None, :]
        T = T * soft.coeff_mask(B)
        C = np.conj(np.asarray(soft.inverse_soft(jnp.asarray(T))))
        out.append(peak_euler(C, B, norm=pair_norm(query, g)))
    return out


@pytest.fixture(scope="module")
def host():
    bank = _bank()
    query = _query(bank)
    res = _host_results(query, bank)
    return bank, query, res, int(np.argmax([r.rank_key for r in res]))


@pytest.mark.parametrize("dtype,tol", [(jnp.float64, 1e-9),
                                       (jnp.float32, 2e-5)])
def test_device_bank_path_matches_the_f64_host_path(host, dtype, tol):
    bank, query, ref, ref_best = host
    eng = plan(B, dtype, impl="fused", V=V, tk=4).engine()
    eng.reset_stats()
    best, res = eng.match_bank(query, bank)
    assert best == ref_best == 6
    assert len(res) == M
    scale = max(abs(r.peak) for r in ref)
    for got, want in zip(res, ref):
        assert got.index == want.index
        np.testing.assert_allclose(got.euler, want.euler, atol=tol)
        assert abs(got.peak - want.peak) <= tol * scale
        assert got.score == pytest.approx(want.score, abs=tol)
        np.testing.assert_allclose(got.stencil, want.stencil,
                                   atol=tol * scale)
    assert eng.stats == dict(launches=2, transforms=M, padded_lanes=V * 2 - M)


def test_stacked_array_list_and_resident_bank_agree(host):
    bank, query, _, _ = host
    eng = plan(B, jnp.float64, impl="fused", V=V, tk=4).engine()
    best, res = eng.match_bank(query, bank)
    keys = [result_key(r) for r in res]
    stacked = np.stack([np.asarray(g) for g in bank])
    resident = eng.load_bank(stacked)
    assert isinstance(resident, TemplateBank) and len(resident) == M
    assert resident.coeffs.shape == (2, V, B, 2 * B - 1)
    for b in (stacked, resident, resident):    # the resident bank, reused
        best_b, res_b = eng.match_bank(query, b)
        assert best_b == best
        assert [result_key(r) for r in res_b] == keys
    assert eng.load_bank(resident) is resident


def test_bank_of_another_engine_is_refused(host):
    bank = host[0]
    resident = plan(B, jnp.float64, impl="fused", V=V, tk=4).engine() \
        .load_bank(bank)
    other = plan(B, jnp.float64, impl="fused", V=4, tk=4).engine()
    with pytest.raises(ValueError, match="does not fit"):
        other.match_bank(host[1], resident)
    with pytest.raises(ValueError, match="empty template bank"):
        other.match_bank(host[1], [])


def test_one_readback_of_a_few_bytes_a_template_and_the_spans(host):
    bank, query, _, _ = host
    eng = plan(B, jnp.float32, impl="fused", V=V, tk=4).engine()
    resident = eng.load_bank(bank)
    eng.match_bank(query, resident)          # compiles the chain
    rec = obs.Recorder()
    old = obs.set_recorder(rec)
    try:
        eng.match_bank(query, resident)
    finally:
        obs.set_recorder(old)
    q = rec.quantiles("correlate.readback_bytes")
    assert q["count"] == 1                   # one readback per query
    # per template lane: an int32 index and 7 f32 stencil values; no grid
    assert q["max"] == 2 * V * (4 + 7 * 4) + 4
    assert q["max"] < (2 * B) ** 3 * 8
    names = [e["name"] for e in rec.events()]
    for name in ("correlate.bank", "correlate.dispatch", "correlate.wait",
                 "correlate.readback", "correlate.refine"):
        assert names.count(name) == 1, name
    # the whole query is one launch of the jitted loop over both chunks
    chunk = [e for e in rec.events() if e["name"] == "executor.chunk"]
    assert len(chunk) == 1 and chunk[0]["args"]["chunks"] == 2
    assert "correlate.pair" not in names      # pairs form on the device
    top = next(e for e in rec.events() if e["name"] == "correlate.bank")
    assert top["args"] == {"B": B, "templates": M, "chunks": 2}


def test_legacy_engine_bank_path_with_partial_lanes():
    """The keyword-form engine at V = 3: four chunks, one lane padded."""
    bank = _bank(seed=40)
    query = _query(bank, planted=2)
    eng = CorrelationEngine(B, lane_width=3, tk=4)
    best, res = eng.match_bank(query, bank)
    assert best == 2
    assert eng.stats == dict(launches=4, transforms=M, padded_lanes=1)
    ref = eng.match_batch([query] * M, bank)
    assert [r.index for r in res] == [r.index for r in ref]
