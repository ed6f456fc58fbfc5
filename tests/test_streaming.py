"""Streaming l-chunked fused DWT schedules (kernels/streaming.py): parity
with the monolithic kernel across chunk sizes (bitwise where the panel
products group their sums alike), the bf16 storage
precision against its error-table gate, the chunked window-table emission
against the core numpy oracle and the dense fundamental table, the
/L{lchunk}/P{precision} cache-key identity, and the planner's static
auto-engagement under a tight VMEM budget."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import plan as plan_mod
from repro.core import batched, quadrature, soft, wigner
from repro.kernels import autotune, ops, streaming


# ---------------------------------------------------------------------------
# parity: chunked == monolithic (fp32/f64)
#
# Every chunk generates the monolithic kernel's rows bit for bit, and both
# contract them on the MXU a panel at a time (P = lchunk for the chunked
# kernel, P = B or a VMEM-bound multiple of 8 for the monolithic one).
# Where the panels are alike the sums group alike and the results are
# bitwise equal, forward and inverse: lchunk = B, and lchunk = 8 at B = 16
# against a monolithic plan whose budget holds only an 8-row panel.  A chunk no
# monolithic panel can match (lchunk = 1, 2, 4) agrees to the dtype's
# rounding (rtol 1e-12 in f64, 1e-5 in f32): the inverse adds B/lchunk
# chunk products where the monolithic kernel takes fewer, and the
# forward's (C2 x J) . (J x P) product puts the panel's P degrees on the
# output's lanes, where XLA's CPU backend, which runs the interpreted
# kernels, sums over J in an order that depends on that width.  Both
# agree with the f64 reference (core/soft.py).
# ---------------------------------------------------------------------------

def _assert_agree(got, want, bitwise, rtol):
    if bitwise:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=rtol,
                                   atol=rtol * np.abs(want).max())


def _monolithic_with_panel(B, lc, dtype=jnp.float64, tk=4):
    """The V = 1 monolithic plan whose Wigner panel is lc rows deep where
    a panel can be (a multiple of 8, or B), else the default panel P = B.
    V = 1 because a single transform runs the one-lane kernel, whose
    panel is the one the schedule reports."""
    V = 1
    base = plan_mod.plan(B, dtype, impl="fused", V=V, tk=tk)
    if lc % 8 or lc == B:
        return base
    C = base.soft_plan.gather_m.shape[1]
    budget = autotune.estimate_vmem_bytes(
        L=B, J=2 * B, C2=V * C * 2, tk=tk,
        itemsize=jnp.dtype(dtype).itemsize, panel=lc)
    mono = plan_mod.plan(B, dtype, impl="fused", V=V, tk=tk,
                         vmem_budget=budget)
    assert mono.schedule.lchunk is None and mono.describe()["panel"] == lc
    return mono


@pytest.mark.parametrize("B", [8, 16])
@pytest.mark.parametrize("lchunk", [1, 2, "B/2", "B"])
def test_streaming_bitwise_equals_monolithic(B, lchunk):
    lc = {"B": B, "B/2": B // 2}.get(lchunk, lchunk)
    mono = _monolithic_with_panel(B, lc)
    strm = plan_mod.plan(B, impl="fused", V=1, tk=4, lchunk=lc)
    assert strm is not mono                    # distinct cache entries
    assert strm.schedule.lchunk == lc
    bitwise = mono.describe()["panel"] == lc
    fhat = soft.random_coeffs(B, seed=B)
    f_mono = np.asarray(mono.inverse(fhat))
    f_strm = np.asarray(strm.inverse(fhat))
    _assert_agree(f_strm, f_mono, bitwise, 1e-12)
    b_mono = np.asarray(mono.forward(f_mono))
    b_strm = np.asarray(strm.forward(f_mono))
    _assert_agree(b_strm, b_mono, bitwise, 1e-12)
    d = wigner.wigner_d_table(B)
    f_ref = np.asarray(soft.inverse_soft(fhat, d))
    np.testing.assert_allclose(f_strm, f_ref, rtol=1e-11, atol=1e-11)
    np.testing.assert_allclose(b_strm, np.asarray(soft.forward_soft(
        f_mono, B, d)), rtol=1e-11, atol=1e-11)


def test_streaming_bitwise_equals_monolithic_f32():
    B = 16
    mono = plan_mod.plan(B, dtype=jnp.float32, impl="fused", V=2, tk=4)
    strm = plan_mod.plan(B, dtype=jnp.float32, impl="fused", V=2, tk=4,
                         lchunk=4)
    fhat = soft.random_coeffs(B, seed=3).astype(np.complex64)
    f_mono = np.asarray(mono.inverse(fhat))
    f_strm = np.asarray(strm.inverse(fhat))
    _assert_agree(f_strm, f_mono, False, 1e-5)
    np.testing.assert_array_equal(np.asarray(strm.forward(f_mono)),
                                  np.asarray(mono.forward(f_mono)))
    f_ref = np.asarray(soft.inverse_soft(fhat.astype(np.complex128)))
    rel = np.abs(f_strm - f_ref).max() / np.abs(f_ref).max()
    assert rel <= autotune.FP32_ROUNDTRIP_BOUNDS[B]


# ---------------------------------------------------------------------------
# bf16 storage precision: bounded by (and distinct from) fp32
# ---------------------------------------------------------------------------

def test_bf16_within_error_table_gate():
    B = 16
    bound = autotune.PRECISION_ERROR_BOUNDS[B]
    mono = plan_mod.plan(B, dtype=jnp.float32, impl="fused", V=2, tk=4)
    bf = plan_mod.plan(B, dtype=jnp.float32, impl="fused", V=2, tk=4,
                       lchunk=4, precision="bf16")
    assert bf.schedule.precision == "bf16"
    fhat = soft.random_coeffs(B, seed=5).astype(np.complex64)
    f32 = np.asarray(mono.inverse(fhat))
    f16 = np.asarray(bf.inverse(fhat))
    rel = np.abs(f16 - f32).max() / np.abs(f32).max()
    assert 0 < rel <= bound                 # rounds, but inside the gate
    b32 = np.asarray(mono.forward(f32))
    b16 = np.asarray(bf.forward(f32))
    rel = np.abs(b16 - b32).max() / np.abs(b32).max()
    assert 0 < rel <= bound


@pytest.mark.parametrize("B", [8, 16, 32])
@pytest.mark.parametrize("member", ["monolithic", "streaming"])
def test_fp32_roundtrip_within_recorded_bounds(B, member):
    """Accuracy-regression guard for the in-kernel f32 Wigner recurrence
    drift (ROADMAP's fp32 accuracy cliff: ~2.2e-3 in d by l = 127 at
    B = 128).  The measured fp32 fused roundtrip max-rel per bandwidth is
    recorded with headroom in autotune.FP32_ROUNDTRIP_BOUNDS; a
    recurrence/seed change that worsens the drift trips this gate instead
    of silently degrading f32 serving accuracy.  The streaming member
    (B/2-row chunks, resumed from two-row windows) meets the same gate."""
    bound = autotune.FP32_ROUNDTRIP_BOUNDS[B]
    lchunk = B // 2 if member == "streaming" else None
    t32 = plan_mod.plan(B, dtype=jnp.float32, impl="fused", tk=4,
                        lchunk=lchunk)
    assert t32.schedule.lchunk == lchunk
    mask = soft.coeff_mask(B)
    worst = 0.0
    for seed in range(3):
        fhat = soft.random_coeffs(B, seed=seed).astype(np.complex64)
        back = np.asarray(t32.forward(t32.inverse(fhat)))
        err = np.abs(back - np.asarray(fhat))[mask]
        ref = np.abs(np.asarray(fhat))[mask]
        worst = max(worst, float((err / np.maximum(ref, 1e-300)).max()))
    assert 0 < worst <= bound


def test_fp32_bounds_cover_the_bf16_ladder():
    """Every bandwidth the bf16 gate covers below paper scale also has an
    fp32 roundtrip gate: the two tables rank the same precision-ladder
    rungs, so a ladder extension cannot add a bf16 bound without first
    measuring the fp32 baseline it is judged against."""
    bf16_small = {B for B in autotune.PRECISION_ERROR_BOUNDS if B <= 128}
    assert bf16_small <= set(autotune.FP32_ROUNDTRIP_BOUNDS)


# ---------------------------------------------------------------------------
# precision resolution: None never downgrades; "auto" is opt-in + dtype-gated
# ---------------------------------------------------------------------------

def test_static_precision_default_never_downgrades():
    # None (the planner default) is fp32 at EVERY bandwidth, including
    # paper-scale ones with a recorded bf16 bound: a default plan(B)
    # must never silently trade accuracy
    for B in (16, 128, 512):
        assert autotune.static_precision(B) == "fp32"
    # explicit choices are honored verbatim
    assert autotune.static_precision(8, "bf16") == "bf16"
    assert autotune.static_precision(512, "fp32") == "fp32"
    with pytest.raises(ValueError, match="precision"):
        autotune.static_precision(8, "fp16")


def test_static_precision_auto_gates_on_dtype_and_bound():
    # "auto" engages bf16 only for fp32 plans at gated paper-scale B
    assert autotune.static_precision(128, "auto",
                                     dtype=jnp.float32) == "bf16"
    assert autotune.static_precision(64, "auto",
                                     dtype=jnp.float32) == "fp32"
    # an f64 plan is NEVER implicitly downgraded, at any bandwidth
    assert autotune.static_precision(128, "auto",
                                     dtype=jnp.float64) == "fp32"
    assert autotune.static_precision(512, "auto",
                                     dtype=jnp.float64) == "fp32"
    # below the threshold "auto" keeps the bitwise path on a real plan
    t = plan_mod.plan(16, dtype=jnp.float32, impl="fused", V=2, tk=4,
                      precision="auto")
    assert t.schedule.precision == "fp32" and t.schedule.lchunk is None


def test_bf16_schedule_records_the_streaming_kernel():
    # bf16 with lchunk=None forces the streaming kernel: the resolved
    # schedule must record a concrete chunk, and its VMEM estimate must
    # model the streaming footprint, not the monolithic one
    t = plan_mod.plan(16, dtype=jnp.float32, impl="fused", V=2, tk=4,
                      precision="bf16")
    s = t.schedule
    assert s.precision == "bf16" and s.lchunk is not None
    K, L, J = t.soft_plan.d.shape
    C = t.soft_plan.gather_m.shape[1]
    assert s.vmem_bytes == autotune.estimate_vmem_bytes(
        L=L, J=J, C2=s.V * C * 2, tk=s.tk, itemsize=4,
        lchunk=s.lchunk, precision="bf16")
    # and the plan matches its explicitly-chunked twin bit for bit
    tw = plan_mod.plan(16, dtype=jnp.float32, impl="fused", V=2, tk=4,
                       lchunk=s.lchunk, precision="bf16")
    fhat = soft.random_coeffs(16, seed=9).astype(np.complex64)
    np.testing.assert_array_equal(np.asarray(t.inverse(fhat)),
                                  np.asarray(tw.inverse(fhat)))


# ---------------------------------------------------------------------------
# window tables: jnp builder == numpy core oracle == dense table boundaries
# ---------------------------------------------------------------------------

def test_build_windows_matches_core_oracle_and_dense_table():
    B, lchunk = 16, 4
    win, pairs = wigner.wigner_window_table(B, lchunk)
    beta = quadrature.betas(B)
    m, mp = pairs[:, 0], pairs[:, 1]
    seeds = np.stack([wigner.wigner_seed(int(a), int(b), beta)
                      for a, b in pairs])
    jwin = np.asarray(streaming.build_windows(
        jnp.asarray(seeds), jnp.asarray(m, jnp.float64)[:, None],
        jnp.asarray(mp, jnp.float64)[:, None],
        jnp.asarray(np.cos(beta))[None, :], L=B, lchunk=lchunk))
    np.testing.assert_allclose(jwin, win, atol=1e-12)
    assert not win[0].any()                  # chunk 0 carries no history
    fund, _ = wigner.wigner_d_fundamental(B)
    for c in range(1, B // lchunk):
        l = c * lchunk
        act = m < l       # pairs seeded at l sit inside the chunk: zeros
        np.testing.assert_allclose(win[c, 1][act], fund[act, l, :],
                                   atol=1e-12)
        np.testing.assert_allclose(win[c, 0][act], fund[act, l - 1, :],
                                   atol=1e-12)
        assert not win[c][:, ~act].any()


def test_window_table_rejects_bad_lchunk():
    with pytest.raises(ValueError, match="divide"):
        wigner.wigner_window_table(16, 3)
    with pytest.raises(ValueError, match="outside"):
        streaming.check_lchunk(16, 0)
    with pytest.raises(ValueError, match="outside"):
        streaming.check_lchunk(16, 17)
    with pytest.raises(ValueError, match="divide"):
        streaming.check_lchunk(16, 6)
    assert streaming.check_lchunk(16, 4) == 4


# ---------------------------------------------------------------------------
# argument validation: streaming exists only for the fused family
# ---------------------------------------------------------------------------

def test_streaming_args_rejected_off_fused():
    """lchunk and bf16 select the streaming kernels; impl="reference" has
    none and refuses them."""
    assert ops._check_streaming_args(2, None) is True
    assert ops._check_streaming_args(None, "bf16") is True
    assert ops._check_streaming_args(None, "fp32") is False
    assert ops._check_streaming_args(None, None) is False
    with pytest.raises(ValueError, match="precision"):
        ops._check_streaming_args(None, "fp16")
    with pytest.raises(ValueError, match="fused"):
        plan_mod.plan(8, impl="reference", lchunk=2)
    with pytest.raises(ValueError, match="fused"):
        plan_mod.plan(8, impl="reference", precision="bf16")
    with pytest.raises(ValueError, match="precision"):
        plan_mod.plan(8, impl="fused", precision="fp16")
    with pytest.raises(ValueError, match="divide"):
        plan_mod.plan(8, impl="fused", lchunk=3)


# ---------------------------------------------------------------------------
# cache-key identity: /L and /P segments key the streaming schedules
# ---------------------------------------------------------------------------

def test_cache_key_has_lchunk_and_precision_segments():
    sp = plan_mod.plan(8, impl="fused", V=2, tk=4).soft_plan
    base = autotune._key(sp, "fused", 2, 1 << 20)
    assert "/L0/Pfp32" in base
    chunked = autotune._key(sp, "fused", 2, 1 << 20, lchunk=4)
    assert "/L4/Pfp32" in chunked and chunked != base
    bf = autotune._key(sp, "fused", 2, 1 << 20, lchunk=4, precision="bf16")
    assert "/L4/Pbf16" in bf and bf != chunked


def test_plan_cache_distinct_per_lchunk_and_precision():
    a = plan_mod.plan(8, impl="fused", V=2, tk=4)
    b = plan_mod.plan(8, impl="fused", V=2, tk=4, lchunk=2)
    c = plan_mod.plan(8, impl="fused", V=2, tk=4, lchunk=2,
                      precision="fp32")
    d = plan_mod.plan(8, dtype=jnp.float32, impl="fused", V=2, tk=4,
                      lchunk=2, precision="bf16")
    assert a is not b and b is not d
    assert b is plan_mod.plan(8, impl="fused", V=2, tk=4, lchunk=2)
    assert c.schedule.lchunk == 2 and c.schedule.precision == "fp32"


# ---------------------------------------------------------------------------
# describe(): memory estimates surface, and chunking shrinks the live tile
# ---------------------------------------------------------------------------

def test_describe_reports_streaming_fields_and_live_memory_drop():
    mono = plan_mod.plan(16, impl="fused", V=2, tk=4).describe()
    strm = plan_mod.plan(16, impl="fused", V=2, tk=4, lchunk=2).describe()
    for d in (mono, strm):
        for key in ("lchunk", "precision", "est_live_coeff_bytes",
                    "est_peak_hbm_bytes"):
            assert key in d
    assert mono["lchunk"] is None and strm["lchunk"] == 2
    assert strm["est_live_coeff_bytes"] < mono["est_live_coeff_bytes"]
    assert strm["est_live_coeff_bytes"] == \
        mono["est_live_coeff_bytes"] * 2 // 16
    # the chunk-boundary window table is HBM the monolithic recurrence
    # never stores; coarser chunks mean fewer boundaries, hence less HBM.
    coarse = plan_mod.plan(16, impl="fused", V=2, tk=4,
                           lchunk=8).describe()
    assert strm["est_peak_hbm_bytes"] > mono["est_peak_hbm_bytes"]
    assert coarse["est_peak_hbm_bytes"] < strm["est_peak_hbm_bytes"]


# ---------------------------------------------------------------------------
# window-built (d-free) plans: bitwise parity with dense-built plans for
# every member of the fused family, loud guards on dense-only consumers
# ---------------------------------------------------------------------------

STREAM_LADDER = [
    pytest.param(dict(impl="fused", dtype=jnp.float64), id="fused"),
    pytest.param(dict(impl="fused", dtype=jnp.float64, lchunk=2),
                 id="fused-lchunk"),
    pytest.param(dict(impl="fused", dtype=jnp.float32, lchunk=2,
                      precision="bf16"), id="fused-bf16"),
    pytest.param(dict(impl="fused", dtype=jnp.float64, V=4),
                 id="fused-lanes"),
]


@pytest.mark.parametrize("B", [4, 8, 16])
@pytest.mark.parametrize("cfg", STREAM_LADDER)
def test_window_built_plan_bitwise_equals_dense_built(B, cfg):
    """streaming=True builds the plan without ever materializing the
    dense (K, L, J) d table -- and the result must be bitwise-identical
    to the dense-built plan under every member of the fused family,
    forward AND inverse (the PR's core acceptance criterion).  The V = 4
    case runs the lane path: forward_batch and inverse_lanes."""
    kw = dict(cfg)
    dtype = kw.pop("dtype")
    V = kw.pop("V", 1)
    td = plan_mod.plan(B, dtype=dtype, V=V, tk=4, **kw)
    ts = plan_mod.plan(B, dtype=dtype, V=V, tk=4, streaming=True, **kw)
    assert not td.soft_plan.streaming and td.soft_plan.d is not None
    assert ts.soft_plan.streaming and ts.soft_plan.d is None
    assert ts.soft_plan.dtype == td.soft_plan.dtype
    cd = np.complex64 if dtype == jnp.float32 else np.complex128
    if V > 1:
        fhats = jnp.stack([soft.random_coeffs(B, seed=B + v).astype(cd)
                           for v in range(V)])
        f = np.asarray(td.inverse_lanes(fhats))
        np.testing.assert_array_equal(np.asarray(ts.inverse_lanes(fhats)), f)
        np.testing.assert_array_equal(
            np.asarray(ts.forward_batch(jnp.asarray(f))),
            np.asarray(td.forward_batch(jnp.asarray(f))))
        return
    fhat = soft.random_coeffs(B, seed=B).astype(cd)
    f = np.asarray(td.inverse(fhat))
    np.testing.assert_array_equal(np.asarray(ts.inverse(fhat)), f)
    np.testing.assert_array_equal(np.asarray(ts.forward(jnp.asarray(f))),
                                  np.asarray(td.forward(jnp.asarray(f))))


def test_streaming_plan_cache_and_soft_plan_identity():
    a = plan_mod.plan(8, impl="fused", V=2, tk=4, streaming=True)
    assert a is plan_mod.plan(8, impl="fused", V=2, tk=4, streaming=True)
    d = plan_mod.plan(8, impl="fused", V=2, tk=4)
    assert a is not d                       # streaming keys its own entry
    assert a.describe()["streaming"] and not d.describe()["streaming"]
    # the d-free SoftPlan rides the same byte-bounded cache
    assert batched.build_plan(8, dtype=jnp.float64, pad_to=4,
                              streaming=True) is a.soft_plan
    assert batched.build_plan(8, dtype=jnp.float64, pad_to=4) is d.soft_plan


def test_window_built_plan_padded_permuted_order():
    """Padding + an explicit cluster permutation flow through the d-free
    build identically to the dense build (bitwise, fwd + inv)."""
    B, K = 8, 8 * 9 // 2
    order = np.random.default_rng(1).permutation(K)
    pd = batched.build_plan(B, dtype=jnp.float64, pad_to=8, order=order)
    ps = batched.build_plan(B, dtype=jnp.float64, pad_to=8, order=order,
                            streaming=True)
    assert ps is not pd and ps.streaming
    np.testing.assert_array_equal(np.asarray(ps.gather_m),
                                  np.asarray(pd.gather_m))
    fhat = jnp.asarray(soft.random_coeffs(B, seed=11))
    f_d = np.asarray(batched.inverse_clustered(
        pd, fhat, idwt_fn=ops.make_idwt_fn(pd, tk=4)))
    f_s = np.asarray(batched.inverse_clustered(
        ps, fhat, idwt_fn=ops.make_idwt_fn(ps, tk=4)))
    np.testing.assert_array_equal(f_s, f_d)
    b_d = np.asarray(batched.forward_clustered(
        pd, jnp.asarray(f_d), dwt_fn=ops.make_dwt_fn(pd, tk=4)))
    b_s = np.asarray(batched.forward_clustered(
        ps, jnp.asarray(f_s), dwt_fn=ops.make_dwt_fn(ps, tk=4)))
    np.testing.assert_array_equal(b_s, b_d)


def test_streaming_plan_rejects_dense_only_consumers():
    sp = batched.build_plan(8, dtype=jnp.float64, pad_to=4, streaming=True)
    for consumer in (lambda: batched.dwt_apply(sp, jnp.zeros(())),
                     lambda: batched.idwt_apply(sp, jnp.zeros(())),
                     lambda: batched.forward_clustered(
                         sp, jnp.zeros((16, 16, 16))),
                     lambda: batched.inverse_clustered(
                         sp, jnp.zeros((8, 15, 15)))):
        with pytest.raises(ValueError, match="streaming"):
            consumer()
    with pytest.raises(ValueError, match="streaming"):
        plan_mod.plan(8, impl="reference", streaming=True)


def test_host_window_stack_matches_device_windows(monkeypatch):
    """The host-generator loader (O(P*J) working set, one staging buffer)
    agrees with the default device march to f64 roundoff, and
    $REPRO_WINDOW_SOURCE=host routes streaming_inputs through it."""
    sp = batched.build_plan(16, dtype=jnp.float64, pad_to=4, streaming=True)
    tk, lchunk = 4, 4
    dev = ops.streaming_inputs(sp, tk, lchunk, "fp32")[-1]
    host = ops.host_window_stack(sp, tk, lchunk)
    assert host.shape == dev.shape == (16 // lchunk, 2, sp.n_padded, 32)
    np.testing.assert_allclose(np.asarray(host), np.asarray(dev),
                               atol=1e-12)
    monkeypatch.setenv("REPRO_WINDOW_SOURCE", "host")
    assert ops.window_source() == "host"
    via_env = ops.streaming_inputs(sp, tk, lchunk, "fp32")[-1]
    np.testing.assert_array_equal(np.asarray(via_env), np.asarray(host))
    monkeypatch.setenv("REPRO_WINDOW_SOURCE", "banana")
    with pytest.raises(ValueError, match="REPRO_WINDOW_SOURCE"):
        ops.window_source()


def test_wigner_window_iter_matches_table():
    """The constant-memory generator and the stacked table are the same
    march -- bitwise, chunk for chunk."""
    for B, lchunk in ((8, 2), (16, 4)):
        win, pairs = wigner.wigner_window_table(B, lchunk)
        chunks = list(wigner.wigner_window_iter(B, lchunk))
        assert len(chunks) == B // lchunk
        np.testing.assert_array_equal(np.stack(chunks), win)
        assert chunks[0].shape == (2, len(pairs), 2 * B)
        assert not chunks[0].any()           # chunk 0 carries no history


def test_static_schedule_auto_engages_streaming_under_tight_budget():
    # A compiled chunk sits on the lanes, so the resolver's candidates are
    # multiples of 128 or B on every backend; plans at B = 256 resolve
    # and build on the CPU without running a kernel.  bf16 has no
    # monolithic kernel: a budget that holds the 128-row chunk but not
    # the whole degree range engages the 128-row chunk at the widest lane
    # width.
    wide = plan_mod.plan(256, jnp.float32, precision="bf16", interpret=False)
    assert wide.schedule.lchunk == 256 and wide.V == 8
    budget = wide.schedule.vmem_bytes - 1
    t = plan_mod.plan(256, jnp.float32, precision="bf16", interpret=False,
                      vmem_budget=budget)
    assert (t.V, t.schedule.lchunk) == (8, 128)
    assert t.schedule.vmem_bytes <= budget
    # fp32 keeps the monolithic kernel, down to an 8-row panel: a chunk's
    # panel is the chunk, and 128 rows of it need more VMEM than the
    # coefficient tile saves, so where no monolithic panel fits at this
    # B, no compiled chunk does
    f = plan_mod.plan(256, jnp.float32, interpret=False, vmem_budget=budget)
    assert f.schedule.lchunk is None
    C = f.soft_plan.gather_m.shape[1]
    tight = autotune.estimate_vmem_bytes(L=256, J=512, C2=C * 2, tk=8,
                                         panel=8) - 1
    with pytest.raises(ValueError, match="no schedule fits"):
        plan_mod.plan(256, jnp.float32, interpret=False, vmem_budget=tight)
    # interpreted, an explicit 8-row chunk at B = 16 sums the same two
    # products as the monolithic kernel under a budget that admits only
    # an 8-row panel
    strm = plan_mod.plan(16, dtype=jnp.float32, impl="fused", V=1, tk=8,
                         lchunk=8)
    ref = _monolithic_with_panel(16, 8, jnp.float32, tk=8)
    fhat = soft.random_coeffs(16, seed=7).astype(np.complex64)
    f_ref = np.asarray(ref.inverse(fhat))
    np.testing.assert_array_equal(np.asarray(strm.inverse(fhat)), f_ref)
    np.testing.assert_array_equal(np.asarray(strm.forward(f_ref)),
                                  np.asarray(ref.forward(f_ref)))
