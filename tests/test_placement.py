"""Placement of cluster members into FFT bins and dense coefficients.

The plan carries two inverse index tables, ``bin_src`` ((2B)^2,) and
``coeff_src`` ((2B-1)^2,): the flat member index k*C + c that fills each
cell, out of range where no member does.  The grid stages place members
with gathers by these tables.  These tests hold the gathers bitwise to a
numpy scatter written here as the scatter-based placement did it (zero
buffer with a trash cell, member rows written in, trash sliced off), check
the tables themselves, check that a permuted, padded plan places alike,
and guard that no XLA scatter comes back into the compiled transforms.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan as plan_mod
from repro.core import batched, parallel, soft

BS = [4, 8, 16, 32]
KINDS = ["streaming", "dense"]
LANES = [1, 4]


def _plan(B, kind, **kw):
    return batched.build_plan(B, dtype=jnp.float64,
                              streaming=kind == "streaming", **kw)


def _members(p, shape, seed):
    """Random complex member values; unused slots hold values too, which a
    placement must never read."""
    r = np.random.default_rng(seed)
    return r.normal(size=shape) + 1j * r.normal(size=shape)


def _rows(x):
    """Complex members (..., K, A, C) -> the member rows (..., K, C*2, A)
    the grid stages and kernels exchange: member c's real part in row 2c,
    its imaginary part in row 2c+1."""
    y = np.swapaxes(x, -1, -2)
    y = np.stack([y.real, y.imag], axis=-2)
    return y.reshape(*y.shape[:-3], -1, y.shape[-1])


def _np_bins(p, g):
    """g[k, j, c] -> FFT bins (2B, j, 2B), by the scatter the bins used to
    be placed with: reflection, then every used slot written into a
    (2B+1, j, 2B+1) zero buffer, unused slots to the trash bin 2B."""
    B = p.B
    sign, refl = np.asarray(p.sign), np.asarray(p.reflected)
    g = np.where(refl[:, None, :], g[:, ::-1, :], g)
    gm = np.where(sign != 0, np.asarray(p.gather_m), 2 * B).reshape(-1)
    gmp = np.where(sign != 0, np.asarray(p.gather_mp), 2 * B).reshape(-1)
    buf = np.zeros((2 * B + 1, g.shape[1], 2 * B + 1), g.dtype)
    buf[gm, :, gmp] = np.swapaxes(g, 1, 2).reshape(-1, g.shape[1])
    return buf[: 2 * B, :, : 2 * B]


def _np_coeffs(p, out):
    """out[k, l, c] -> dense (L, 2B-1, 2B-1), by the old scatter: unused
    slots land on the trash cell 2B-1, which is sliced off."""
    B = p.B
    buf = np.zeros((B, 2 * B, 2 * B), out.dtype)
    buf[:, np.asarray(p.scatter_m).reshape(-1),
        np.asarray(p.scatter_mp).reshape(-1)] = \
        out.transpose(1, 0, 2).reshape(B, -1)
    return buf[:, : 2 * B - 1, : 2 * B - 1]


def _scaled(p, out):
    """The forward's output sign and scale, as _output_coeffs applies it."""
    sgn = jnp.where(p.reflected[:, None, :], p.parity[None, :, None],
                    jnp.ones((), p.parity.dtype))
    return np.asarray(jnp.asarray(out) * (sgn * p.scale[None, :, None]))


# ---------------------------------------------------------------------------
# bitwise parity with the scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V", LANES)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B", BS)
def test_bins_bitwise_equal_scatter(B, kind, V):
    p = _plan(B, kind)
    g = _members(p, (V, p.n_padded, 2 * B, 8), seed=B + V)
    want = np.stack([_np_bins(p, x) for x in g])
    rows = _rows(g)
    got = np.asarray(jax.vmap(lambda x: batched._place_bins(p, x))(rows))
    np.testing.assert_array_equal(got, want)
    # the slab path: the same bins through the same FFT
    syn = np.asarray(jax.vmap(lambda x: batched.streamed_synthesis(p, x))(
        rows))
    ref = np.asarray(jax.vmap(batched.fft_synthesis)(jnp.asarray(want)))
    np.testing.assert_array_equal(syn, ref)


@pytest.mark.parametrize("V", LANES)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B", BS)
def test_coeffs_bitwise_equal_scatter(B, kind, V):
    p = _plan(B, kind)
    out = _members(p, (V, p.n_padded, B, 8), seed=100 + B + V)
    want = np.stack([_np_coeffs(p, _scaled(p, x)) for x in out])
    got = np.asarray(jax.vmap(lambda x: batched._output_coeffs(p, x))(
        _rows(out)))
    np.testing.assert_array_equal(got, want)
    dense = np.asarray(parallel.packed_to_dense_batch(p, out))
    np.testing.assert_array_equal(
        dense, np.stack([_np_coeffs(p, x) for x in out]))


# ---------------------------------------------------------------------------
# the tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B", BS)
def test_bin_src_reads_every_valid_member_once(B, kind):
    p = _plan(B, kind)
    n, KC = 2 * B, p.sign.size
    src = np.asarray(p.bin_src)
    assert src.shape == (n * n,) and src.dtype == np.int32
    valid = np.flatnonzero(np.asarray(p.sign).reshape(-1) != 0)
    full = src[src < KC]
    np.testing.assert_array_equal(np.sort(full), valid)
    # each filled bin reads the member whose FFT bin it is
    cells = np.flatnonzero(src < KC)
    np.testing.assert_array_equal(
        np.asarray(p.gather_m).reshape(-1)[full], cells // n)
    np.testing.assert_array_equal(
        np.asarray(p.gather_mp).reshape(-1)[full], cells % n)
    # the empty bins are the Nyquist row and column (bin B), and read 0
    empty = np.flatnonzero(src >= KC)
    assert np.all((empty // n == B) | (empty % n == B))
    assert len(empty) == 4 * B - 1
    bins = np.asarray(batched._place_bins(
        p, _rows(_members(p, (p.n_padded, 2 * B, 8), seed=B))))
    assert not bins[B].any() and not bins[:, :, B].any()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B", BS)
def test_coeff_src_is_a_bijection_onto_valid_members(B, kind):
    p = _plan(B, kind)
    n, KC = 2 * B - 1, p.sign.size
    src = np.asarray(p.coeff_src)
    assert src.shape == (n * n,) and src.dtype == np.int32
    valid = np.flatnonzero(np.asarray(p.sign).reshape(-1) != 0)
    assert np.all(src < KC)                 # every dense cell has a member
    np.testing.assert_array_equal(np.sort(src), valid)
    np.testing.assert_array_equal(
        np.asarray(p.scatter_m).reshape(-1)[src], np.arange(n * n) // n)
    np.testing.assert_array_equal(
        np.asarray(p.scatter_mp).reshape(-1)[src], np.arange(n * n) % n)


def test_member_sources_refuses_shared_cells():
    sign = np.ones((1, 2), np.int8)
    row = np.zeros((1, 2), np.int32)
    with pytest.raises(ValueError, match="share one output cell"):
        batched.member_sources(row, row, sign, 3)


# ---------------------------------------------------------------------------
# permuted, padded plans
# ---------------------------------------------------------------------------

def _permuted(B, kind, n_shards=4):
    nat = _plan(B, kind)
    K = nat.n_clusters
    n_padded = -(-K // n_shards) * n_shards + n_shards   # at least one pad
    order = batched.shard_balanced_order(batched.plan_lstart(nat)[:K],
                                         n_shards, n_padded=n_padded)
    perm = _plan(B, kind, pad_to=n_padded, order=order)
    assert perm.n_padded == n_padded > K
    return nat, perm, order


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B", BS)
def test_permuted_padded_plan_places_alike(B, kind):
    nat, perm, order = _permuted(B, kind)
    K, pad = nat.n_clusters, perm.n_padded - nat.n_clusters

    def permute(x):            # natural-order members -> the permuted plan's
        return np.concatenate([x[order], _members(
            perm, (pad,) + x.shape[1:], seed=7)])   # pad rows are never read

    g = _members(nat, (K, 2 * B, 8), seed=B)
    np.testing.assert_array_equal(
        np.asarray(batched._place_bins(perm, _rows(permute(g)))),
        np.asarray(batched._place_bins(nat, _rows(g))))
    np.testing.assert_array_equal(
        np.asarray(batched.streamed_synthesis(perm, _rows(permute(g)))),
        np.asarray(batched.streamed_synthesis(nat, _rows(g))))
    out = _members(nat, (K, B, 8), seed=B + 1)
    np.testing.assert_array_equal(
        np.asarray(batched._output_coeffs(perm, _rows(permute(out)))),
        np.asarray(batched._output_coeffs(nat, _rows(out))))


@pytest.mark.parametrize("B", [4, 8])
def test_permuted_padded_plan_transforms_alike(B):
    """The whole jnp transform (einsum DWT) on a permuted, padded plan
    matches the natural order's."""
    nat, perm, _ = _permuted(B, "dense")
    fhat = jnp.asarray(soft.random_coeffs(B, seed=B))
    f_nat = np.asarray(batched.inverse_clustered(nat, fhat))
    f_perm = np.asarray(batched.inverse_clustered(perm, fhat))
    np.testing.assert_allclose(f_perm, f_nat, rtol=1e-13, atol=1e-13)
    b_nat = np.asarray(batched.forward_clustered(nat, jnp.asarray(f_nat)))
    b_perm = np.asarray(batched.forward_clustered(perm, jnp.asarray(f_nat)))
    np.testing.assert_allclose(b_perm, b_nat, rtol=1e-13, atol=1e-13)
    np.testing.assert_allclose(b_nat, np.asarray(fhat), rtol=1e-11,
                               atol=1e-11)


# ---------------------------------------------------------------------------
# guard: no XLA scatter in the compiled transforms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("streaming", [None, True])
def test_compiled_transforms_hold_no_scatter(streaming):
    B = 16
    t = plan_mod.plan(B, jnp.float32, streaming=streaming)
    assert t.soft_plan.streaming == bool(streaming)
    t.dwt_fn, t.idwt_fn, t.idwt_fn_batch   # lazy operands, built untraced
    fhat = jax.ShapeDtypeStruct((B, 2 * B - 1, 2 * B - 1), jnp.complex64)
    grid = jax.ShapeDtypeStruct((2 * B,) * 3, jnp.complex64)
    lanes = jax.ShapeDtypeStruct((t.V,) + fhat.shape, jnp.complex64)
    for name, fn, arg in (("inverse", t.inverse, fhat),
                          ("forward", t.forward, grid),
                          ("inverse_lanes", t.inverse_lanes, lanes)):
        hlo = jax.jit(fn).lower(arg).compile().as_text()
        assert "gather(" in hlo, name
        assert "scatter(" not in hlo, name
