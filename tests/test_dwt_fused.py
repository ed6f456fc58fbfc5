"""Fused ragged+on-the-fly DWT kernel: parity against the jnp oracle and
the other schedules, the Wigner panel contraction (full and short panels,
padded clusters, lane batching), multi-transform lane batching, the batch
transform wrappers, and the measured autotuner."""
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
# kernel parity vs the jnp oracle and the sibling schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [4, 8, 16])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_forward_matches_oracle(B, dtype):
    jdt = jnp.float32 if dtype == np.float32 else jnp.float64
    plan = batched.build_plan(B, dtype=jdt, pad_to=4)
    K, L, J = plan.d.shape
    rhs = rand((K, J, 8, 2), dtype, scale=0.3)
    out = np.asarray(ops.make_dwt_fn(plan, "fused", tk=4)(plan, rhs))
    expect = np.asarray(ref.dwt_ref(plan.d, rhs.reshape(K, J, 16)))
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(out.reshape(K, L, 16), expect, rtol=rtol,
                               atol=atol)


@pytest.mark.parametrize("B", [4, 8, 16])
def test_fused_matches_ragged_and_onthefly(B):
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    K, L, J = plan.d.shape
    rhs = rand((K, J, 8, 2))
    fused = np.asarray(ops.make_dwt_fn(plan, "fused", tk=4)(plan, rhs))
    otf = np.asarray(ops.make_dwt_fn(plan, "onthefly", tk=4)(plan, rhs))
    rag = np.asarray(ops.make_dwt_fn(plan, "ragged", tk=4, tl=max(B // 4, 2),
                                     tj=J)(plan, rhs))
    np.testing.assert_allclose(fused, otf, rtol=1e-11, atol=1e-12)
    # ragged masks l < l_start to zero; fused rows there are zero too
    np.testing.assert_allclose(fused, rag, rtol=1e-10, atol=1e-11)


def test_fused_actually_skips_rows():
    """The scalar-prefetch schedule enumerates strictly fewer degree-rows
    than the full-range on-the-fly march."""
    plan = batched.build_plan(16, dtype=jnp.float64, pad_to=8)
    K, L, _ = plan.d.shape
    tk = 8
    _, _, l0s = ops.fused_metadata(plan, tk)
    assert (l0s > 0).any()
    assert int(np.sum(L - l0s)) < (K // tk) * L


def test_fused_inverse_matches_oracle():
    plan = batched.build_plan(8, dtype=jnp.float64, pad_to=4)
    K, L, J = plan.d.shape
    # lhs as produced by _gather_coeffs: zero below each cluster's l-start
    fhat = soft.random_coeffs(8, 5)
    lhs = np.asarray(batched._gather_coeffs(plan, jnp.asarray(fhat)))
    out = np.asarray(ops.make_idwt_fn(plan, "fused", tk=4)(plan, lhs))
    expect = np.asarray(ref.idwt_ref(plan.d, lhs.reshape(K, L, 16)))
    np.testing.assert_allclose(out.reshape(K, J, 16), expect, rtol=1e-10,
                               atol=1e-11)


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
        limit = autotune.estimate_vmem_bytes("fused", L=L, J=J, C2=C2, tk=tk,
                                             itemsize=8, panel=8)
    want_P = 8 if panel == "short" else B
    assert autotune.panel_depth(L=L, J=J, C2=C2, tk=tk, itemsize=8,
                                limit=limit) == want_P
    maker = ops.make_dwt_fn if direction == "fwd" else ops.make_idwt_fn
    fn = maker(plan, "fused", tk=tk, vmem_limit=limit,
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


@pytest.mark.parametrize("impl", ["dense", "ragged", "onthefly", "fused"])
@pytest.mark.parametrize("V", [1, 4])
def test_batched_dwt_matches_per_transform(impl, V):
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    K, L, J = plan.d.shape
    kw = dict(tk=4, tl=4, tj=J)
    single = ops.make_dwt_fn(plan, impl, **kw)
    rhs = rand((V, K, J, 8, 2))
    out = np.asarray(ops.make_dwt_fn(plan, impl, batch=V, **kw)(plan, rhs))
    expect = np.stack([np.asarray(single(plan, rhs[v])) for v in range(V)])
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


@pytest.mark.parametrize("impl", ["fused", "onthefly"])
@pytest.mark.parametrize("V", [1, 4])
def test_batch_transform_roundtrip(impl, V):
    """forward_clustered_batch o inverse_clustered_batch == identity."""
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, s))
                       for s in range(V)])
    idwt_fn = ops.make_idwt_fn(plan, impl, tk=4, batch=V)
    dwt_fn = ops.make_dwt_fn(plan, impl, tk=4, batch=V)
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
    fn = ops.make_dwt_fn(plan, "fused", tk=4, batch=4)
    with pytest.raises(ValueError, match="batch=4"):
        fn(plan, jnp.asarray(rand((2, K, J, 8, 2))))


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_autotune_caches_and_reuses(tmp_path):
    plan = batched.build_plan(8, dtype=jnp.float32, pad_to=4)
    cache = tmp_path / "autotune.json"
    cfg = autotune.autotune_dwt(plan, "fused", cache=cache, reps=1)
    assert cache.exists()
    assert cfg["tk"] >= 1 and cfg["V"] == 1 and cfg["per_transform_s"] > 0
    # second call must hit the cache (identical dict, no re-measure drift)
    assert autotune.autotune_dwt(plan, "fused", cache=cache, reps=1) == cfg
    # tuned fn produces oracle-parity output
    K, L, J = plan.d.shape
    rhs = rand((K, J, 8, 2), np.float32, scale=0.3)
    out = np.asarray(autotune.tuned_dwt_fn(plan, "fused", cache=cache)(plan,
                                                                       rhs))
    expect = np.asarray(ref.dwt_ref(plan.d, rhs.reshape(K, J, 16)))
    np.testing.assert_allclose(out.reshape(K, L, 16), expect, rtol=5e-4,
                               atol=1e-4)


def test_candidate_tiles_respect_divisibility():
    for impl in ("dense", "fused"):
        for cand in autotune.candidate_tiles(24, 16, 32, impl):
            assert 24 % cand["tk"] == 0
            assert 16 % cand["tl"] == 0
            assert 32 % cand["tj"] == 0


# ---------------------------------------------------------------------------
# VMEM-budget guard: wide-V candidates skip instead of failing at compile
# ---------------------------------------------------------------------------

def test_vmem_estimate_grows_with_lanes_and_tiles():
    kw = dict(L=16, J=32, itemsize=4)
    base = autotune.estimate_vmem_bytes("fused", tk=8, C2=16, **kw)
    assert base > 0
    # lane packing (C2 = V*C*2) and cluster tiling both grow the footprint
    assert autotune.estimate_vmem_bytes("fused", tk=8, C2=128, **kw) > base
    assert autotune.estimate_vmem_bytes("fused", tk=16, C2=16, **kw) > base
    # the fused kernels' (tk, P, J) Wigner panel is counted: the whole
    # degree range under the default budget, 4 bytes a row element
    short = autotune.estimate_vmem_bytes("fused", tk=8, C2=16, panel=8, **kw)
    assert base - short == 4 * 8 * (16 - 8) * 32
    assert autotune.estimate_vmem_bytes("onthefly", tk=8, C2=16,
                                        **kw) == base - 4 * 8 * 16 * 32
    # a budget between the two shrinks the panel instead of failing
    assert autotune.estimate_vmem_bytes("fused", tk=8, C2=16, limit=short,
                                        **kw) == short
    # a streaming schedule's panel is its chunk
    assert autotune.estimate_vmem_bytes("fused", tk=8, C2=16, lchunk=8,
                                        **kw) < base
    dense = autotune.estimate_vmem_bytes("dense", tk=8, tl=16, tj=32, C2=16,
                                         **kw)
    assert dense > 0


def test_vmem_limit_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BYTES", "12345")
    assert autotune.vmem_limit_bytes() == 12345


def test_autotune_skips_over_budget_lane_candidates(tmp_path):
    """With a ceiling that only admits the narrowest V=1 candidate, a
    Vs=(1, 8) sweep must degrade gracefully to V=1 -- not die compiling
    the 8-lane kernel."""
    plan = batched.build_plan(8, dtype=jnp.float32, pad_to=4)
    K, L, J = plan.d.shape
    tks = [c["tk"] for c in autotune.candidate_tiles(K, L, J, "fused")]
    limit = autotune.estimate_vmem_bytes("fused", tk=min(tks), C2=16,
                                         L=L, J=J, itemsize=4)
    cfg = autotune.autotune_dwt(plan, "fused", Vs=(1, 8), reps=1,
                                cache=tmp_path / "c.json", vmem_limit=limit)
    assert cfg["V"] == 1 and cfg["tk"] == min(tks)


def test_autotune_all_candidates_over_budget_raises(tmp_path):
    plan = batched.build_plan(8, dtype=jnp.float32, pad_to=4)
    with pytest.raises(RuntimeError, match="VMEM"):
        autotune.autotune_dwt(plan, "fused", Vs=(8,), reps=1,
                              cache=tmp_path / "c.json", vmem_limit=1)
