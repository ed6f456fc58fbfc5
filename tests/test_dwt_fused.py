"""Fused ragged+on-the-fly DWT kernel family: parity against the jnp
oracle over dtypes, bandwidths and cluster tiles, the Wigner panel
contraction (full and short panels, padded clusters, lane batching),
multi-transform lane batching of the monolithic and streaming members,
the batch transform wrappers, and the measured autotuner."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import plan as plan_mod
from repro.core import batched, soft
from repro.kernels import autotune, dwt_fused, ops, ref


RNG = np.random.default_rng(1)


def rand(shape, dtype=np.float64, scale=1.0):
    return (RNG.normal(size=shape) * scale).astype(dtype)


def _tol(dtype):
    return (5e-4, 1e-4) if dtype == np.float32 else (1e-10, 1e-11)


# ---------------------------------------------------------------------------
# kernel parity vs the jnp oracle
# ---------------------------------------------------------------------------

# (B, dtype, tk): every bandwidth in both dtypes at tk = 4, and the f64
# B = 16 case (136 clusters) at every cluster tile that divides it
ORACLE_CASES = [(B, dtype, 4) for B in (4, 8, 16)
                for dtype in (np.float32, np.float64)] + [
    (16, np.float64, 2), (16, np.float64, 8)]


@pytest.mark.parametrize("B,dtype,tk", ORACLE_CASES)
def test_fused_forward_matches_oracle(B, dtype, tk):
    jdt = jnp.float32 if dtype == np.float32 else jnp.float64
    plan = batched.build_plan(B, dtype=jdt, pad_to=4)
    K, L, J = plan.d.shape
    rhs = rand((K, J, 8, 2), dtype, scale=0.3)
    out = np.asarray(ops.make_dwt_fn(plan, tk=tk)(plan, rhs))
    expect = np.asarray(ref.dwt_ref(plan.d, rhs.reshape(K, J, 16)))
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(out.reshape(K, L, 16), expect, rtol=rtol,
                               atol=atol)


def test_fused_actually_skips_rows():
    """The scalar-prefetch schedule enumerates strictly fewer degree-rows
    than the full-range on-the-fly march."""
    plan = batched.build_plan(16, dtype=jnp.float64, pad_to=8)
    K, L, _ = plan.d.shape
    tk = 8
    _, _, l0s = ops.fused_metadata(plan, tk)
    assert (l0s > 0).any()
    assert int(np.sum(L - l0s)) < (K // tk) * L


@pytest.mark.parametrize("B,dtype,tk", ORACLE_CASES)
def test_fused_inverse_matches_oracle(B, dtype, tk):
    jdt = jnp.float32 if dtype == np.float32 else jnp.float64
    cdt = np.complex64 if dtype == np.float32 else np.complex128
    plan = batched.build_plan(B, dtype=jdt, pad_to=4)
    K, L, J = plan.d.shape
    # lhs as produced by _gather_coeffs: zero below each cluster's l-start
    fhat = soft.random_coeffs(B, 5).astype(cdt)
    lhs = np.asarray(batched._gather_coeffs(plan, jnp.asarray(fhat)))
    out = np.asarray(ops.make_idwt_fn(plan, tk=tk)(plan, lhs))
    expect = np.asarray(ref.idwt_ref(plan.d, lhs.reshape(K, L, 16)))
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(out.reshape(K, J, 16), expect, rtol=rtol,
                               atol=atol)


# ---------------------------------------------------------------------------
# the Wigner panel: rows generated into VMEM, one MXU product per cluster
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,panel", [(8, "B"), (16, "B"), (32, "B"),
                                     (16, "short"), (32, "short")])
@pytest.mark.parametrize("V", [1, 2])
@pytest.mark.parametrize("direction", ["fwd", "inv"])
def test_panel_contraction_matches_oracle(B, panel, V, direction):
    """Padded clusters (pad_to=32), tiles whose l0 > 0, and, for "short",
    a VMEM budget that leaves room for an 8-row panel only, so the kernel
    closes B/8 panels per grid step."""
    tk, C2 = 8, V * 16
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=32)
    K, L, J = plan.d.shape
    assert plan.n_padded > plan.n_clusters
    _, l_start, l0s = ops.fused_metadata(plan, tk)
    assert (l0s > 0).any()
    limit = None
    if panel == "short":
        limit = autotune.estimate_vmem_bytes(L=L, J=J, C2=C2, tk=tk,
                                             itemsize=8, panel=8)
    want_P = 8 if panel == "short" else B
    assert autotune.panel_depth(L=L, J=J, C2=C2, tk=tk, itemsize=8,
                                limit=limit) == want_P
    maker = ops.make_dwt_fn if direction == "fwd" else ops.make_idwt_fn
    fn = maker(plan, tk=tk, vmem_limit=limit,
               batch=None if V == 1 else V)
    if direction == "fwd":
        x = rand((V, K, J, 8, 2), scale=0.3)
    else:   # coefficients are zero below each cluster's l-start
        x = rand((V, K, L, 8, 2)) * (np.arange(L)[:, None, None]
                                     >= l_start[:, None, None, None])
    out = np.asarray(fn(plan, x[0] if V == 1 else x)).reshape(V, K, -1, 16)
    oracle = ref.dwt_ref if direction == "fwd" else ref.idwt_ref
    for v in range(V):
        expect = np.asarray(oracle(plan.d, x[v].reshape(K, -1, 16)))
        np.testing.assert_allclose(out[v], expect, rtol=1e-10, atol=1e-11)
    if direction == "fwd":
        # rows below each cluster's l-start, and padded clusters, read zero
        below = np.arange(L)[None, :] < l_start[:, None]
        assert not out[:, below].any()
        assert not out[:, plan.n_clusters:].any()


def test_describe_reports_the_panel():
    """The paper's B = 128 in f32 runs one 128-row panel per grid step:
    one product per cluster.  A streaming schedule's panel is its chunk."""
    t = plan_mod.plan(128, jnp.float32)
    d = t.describe()
    assert (d["impl"], d["V"], d["lchunk"], d["tk"]) == ("fused", 8, None, 8)
    assert d["panel"] == 128
    s = plan_mod.plan(16, dtype=jnp.float32, impl="fused", tk=4, lchunk=8)
    assert s.describe()["panel"] == 8
    assert plan_mod.plan(8, impl="reference").describe()["panel"] is None


# ---------------------------------------------------------------------------
# multi-transform lane batching
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip():
    x = jnp.asarray(rand((3, 4, 6, 8, 2)))
    y = ops.unpack_lanes(ops.pack_lanes(x), 3, 8)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# the monolithic kernel and a streaming member (4-row chunks) at B = 8
MEMBERS = [pytest.param({}, id="monolithic"),
           pytest.param({"lchunk": 4}, id="streaming")]


@pytest.mark.parametrize("member", MEMBERS)
@pytest.mark.parametrize("V", [1, 4])
@pytest.mark.parametrize("direction", ["dwt", "idwt"])
def test_batched_dwt_matches_per_transform(member, V, direction):
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    K, L, J = plan.d.shape
    maker = ops.make_dwt_fn if direction == "dwt" else ops.make_idwt_fn
    single = maker(plan, tk=4, **member)
    rows = J if direction == "dwt" else L
    x = rand((V, K, rows, 8, 2))
    out = np.asarray(maker(plan, tk=4, batch=V, **member)(plan, x))
    expect = np.stack([np.asarray(single(plan, x[v])) for v in range(V)])
    np.testing.assert_allclose(out, expect, rtol=1e-11, atol=1e-12)


def test_batched_rhs_matches_stacked_gather():
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    f = jnp.asarray(rand((3, 2 * B, 2 * B, 2 * B), scale=0.2))
    S = jax.vmap(batched.fft_analysis)(f)
    packed = ops.batched_rhs(plan, S)
    per = jnp.stack([batched._gather_rhs(plan, S[v]) for v in range(3)])
    np.testing.assert_allclose(np.asarray(packed),
                               np.asarray(ops.pack_lanes(per)),
                               rtol=1e-12, atol=1e-13)


@pytest.mark.parametrize("member", MEMBERS)
@pytest.mark.parametrize("V", [1, 4])
def test_batch_transform_roundtrip(member, V):
    """forward_clustered_batch o inverse_clustered_batch == identity."""
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, s))
                       for s in range(V)])
    idwt_fn = ops.make_idwt_fn(plan, tk=4, batch=V, **member)
    dwt_fn = ops.make_dwt_fn(plan, tk=4, batch=V, **member)
    f = batched.inverse_clustered_batch(plan, fhats, idwt_fn=idwt_fn)
    # matches V independent single transforms
    for v in range(V):
        f_ref = batched.inverse_clustered(plan, fhats[v])
        np.testing.assert_allclose(np.asarray(f[v]), np.asarray(f_ref),
                                   rtol=1e-11, atol=1e-11)
    back = batched.forward_clustered_batch(plan, f, dwt_fn=dwt_fn)
    np.testing.assert_allclose(np.asarray(back), np.asarray(fhats),
                               rtol=1e-8, atol=1e-9)


def test_batch_fn_rejects_wrong_batch():
    plan = batched.build_plan(8, dtype=jnp.float64, pad_to=4)
    K, _, J = plan.d.shape
    fn = ops.make_dwt_fn(plan, tk=4, batch=4)
    with pytest.raises(ValueError, match="batch=4"):
        fn(plan, jnp.asarray(rand((2, K, J, 8, 2))))


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_autotune_caches_and_reuses(tmp_path):
    plan = batched.build_plan(8, dtype=jnp.float32, pad_to=4)
    cache = tmp_path / "autotune.json"
    cfg = autotune.autotune_dwt(plan, cache=cache, reps=1)
    assert cache.exists()
    assert cfg["tk"] >= 1 and cfg["V"] == 1 and cfg["per_transform_s"] > 0
    # second call must hit the cache (identical dict, no re-measure drift)
    assert autotune.autotune_dwt(plan, cache=cache, reps=1) == cfg
    # tuned fn produces oracle-parity output
    K, L, J = plan.d.shape
    rhs = rand((K, J, 8, 2), np.float32, scale=0.3)
    out = np.asarray(autotune.tuned_dwt_fn(plan, cache=cache)(plan, rhs))
    expect = np.asarray(ref.dwt_ref(plan.d, rhs.reshape(K, J, 16)))
    np.testing.assert_allclose(out.reshape(K, L, 16), expect, rtol=5e-4,
                               atol=1e-4)


def test_candidate_tiles_respect_divisibility():
    assert autotune.candidate_tiles(24) == [4, 8]
    for K in (24, 36, 136, 7):
        tks = autotune.candidate_tiles(K)
        assert tks and all(K % tk == 0 for tk in tks)
    # a per-device shard no primary tile divides falls back to small ones
    assert autotune.candidate_tiles(18) == [2, 3, 6]


# ---------------------------------------------------------------------------
# VMEM-budget guard: wide-V candidates skip instead of failing at compile
# ---------------------------------------------------------------------------

def test_vmem_estimate_grows_with_lanes_and_tiles():
    kw = dict(L=16, J=32, itemsize=4)
    base = autotune.estimate_vmem_bytes(tk=8, C2=16, **kw)
    assert base > 0
    # lane packing (C2 = V*C*2) and cluster tiling both grow the footprint
    assert autotune.estimate_vmem_bytes(tk=8, C2=128, **kw) > base
    assert autotune.estimate_vmem_bytes(tk=16, C2=16, **kw) > base
    # the fused kernels' (tk, P, J) Wigner panel is counted: the whole
    # degree range under the default budget, 4 bytes a row element
    short = autotune.estimate_vmem_bytes(tk=8, C2=16, panel=8, **kw)
    assert base - short == 4 * 8 * (16 - 8) * 32
    # a budget between the two shrinks the panel instead of failing
    assert autotune.estimate_vmem_bytes(tk=8, C2=16, limit=short,
                                        **kw) == short
    # a streaming schedule's panel is its chunk
    stream = autotune.estimate_vmem_bytes(tk=8, C2=16, lchunk=8, **kw)
    assert stream < base
    # bf16 storage halves the staged (2, tk, J) window block
    assert stream - autotune.estimate_vmem_bytes(
        tk=8, C2=16, lchunk=8, precision="bf16", **kw) == 2 * 2 * 8 * 32


def test_vmem_limit_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BYTES", "12345")
    assert autotune.vmem_limit_bytes() == 12345


def test_autotune_skips_over_budget_lane_candidates(tmp_path):
    """With a ceiling that only admits the narrowest V=1 candidate, a
    Vs=(1, 8) sweep must degrade gracefully to V=1 -- not die compiling
    the 8-lane kernel."""
    plan = batched.build_plan(8, dtype=jnp.float32, pad_to=4)
    K, L, J = plan.d.shape
    tks = autotune.candidate_tiles(K)
    limit = autotune.estimate_vmem_bytes(tk=min(tks), C2=16, L=L, J=J,
                                         itemsize=4)
    cfg = autotune.autotune_dwt(plan, Vs=(1, 8), reps=1,
                                cache=tmp_path / "c.json", vmem_limit=limit)
    assert cfg["V"] == 1 and cfg["tk"] == min(tks)


def test_autotune_all_candidates_over_budget_raises(tmp_path):
    plan = batched.build_plan(8, dtype=jnp.float32, pad_to=4)
    with pytest.raises(RuntimeError, match="VMEM"):
        autotune.autotune_dwt(plan, Vs=(8,), reps=1,
                              cache=tmp_path / "c.json", vmem_limit=1)
