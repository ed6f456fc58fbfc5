"""SO(3) correlation subsystem: S^2 transforms vs the dense oracle,
correlation peak recovery, fused-lane structural checks, and the
continuous-batching service tier (admission, deadlines, retries, typed
shedding, mixed-bandwidth fuzz)."""
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.core import batched, quadrature, soft, wigner
from repro.kernels import dwt_fused as dwt_fused_mod
from repro.so3 import (Cancelled, CorrelationEngine, Expired, Rejected,
                       SO3Service, ServiceError, result_key, s2)
from repro.so3.correlate import (angle_error as ang_err, peak_euler,
                                 random_rotation as hidden_rotation)
from repro.so3.service import infer_bandwidth


def planted_pair(B, seed):
    """(f, g, true): g random, f = Lambda(true) g."""
    true = hidden_rotation(seed)
    g = soft.random_s2_coeffs(B, seed=seed)
    return s2.rotate_s2_coeffs(g, true), g, true


# ---------------------------------------------------------------------------
# S^2 transforms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [4, 8, 16])
def test_s2_roundtrip(B):
    flm = soft.random_s2_coeffs(B, seed=3)
    f = s2.s2_synthesis(flm)
    back = np.asarray(s2.s2_analysis(f, B))
    np.testing.assert_allclose(back, flm, rtol=1e-11, atol=1e-12)
    # analysis is exact on bandlimited samples: synthesize again
    np.testing.assert_allclose(np.asarray(s2.s2_synthesis(back)),
                               np.asarray(f), rtol=1e-11, atol=1e-12)


@pytest.mark.parametrize("B", [4, 8])
def test_s2_synthesis_matches_lifted_so3_oracle(B):
    """An S^2 function IS an SO(3) function constant in gamma: the m' = 0
    coefficient slice through the dense inverse_soft oracle must equal
    s2_synthesis on every gamma slice."""
    flm = soft.random_s2_coeffs(B, seed=5)
    fhat = np.zeros((B, 2 * B - 1, 2 * B - 1), complex)
    fhat[:, :, B - 1] = flm                       # m' = 0 column
    F3 = np.asarray(soft.inverse_soft(jnp.asarray(fhat)))
    f2 = np.asarray(s2.s2_synthesis(flm))
    assert np.abs(F3 - F3[:, :, :1]).max() < 1e-12   # gamma-constant
    np.testing.assert_allclose(F3[:, :, 0], f2, rtol=1e-12, atol=1e-12)
    # and the forward direction: lifted FSOFT == s2_analysis on the slice
    back3 = np.asarray(soft.forward_soft(jnp.asarray(F3), B))
    back2 = np.asarray(s2.s2_analysis(f2, B))
    np.testing.assert_allclose(back3[:, :, B - 1], back2, rtol=1e-10,
                               atol=1e-11)


def test_rotate_rejects_beta_outside_open_interval():
    """Out-of-range beta must fail loudly, not plant NaN coefficients that
    surface as a bogus MatchResult downstream."""
    flm = soft.random_s2_coeffs(4)
    for bad in (4.0, -0.3, 0.0, np.pi):
        with pytest.raises(ValueError, match="beta"):
            s2.rotate_s2_coeffs(flm, (1.0, bad, 2.0))


def test_legendre_columns_match_dense_wigner_table():
    B = 8
    leg = s2.legendre_columns(B)
    d = wigner.wigner_d_table(B)                  # (B, 2B-1, 2B-1, 2B)
    np.testing.assert_allclose(leg, d[:, :, B - 1, :], rtol=0, atol=0)


def test_random_s2_coeffs_seeded_and_masked():
    a = soft.random_s2_coeffs(8, seed=7)
    b = soft.random_s2_coeffs(8, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a[~soft.s2_coeff_mask(8)].max() == 0
    assert np.abs(a[soft.s2_coeff_mask(8)]).min() > 0
    assert not np.array_equal(a, soft.random_s2_coeffs(8, seed=8))


# ---------------------------------------------------------------------------
# correlation: peak recovery of a planted rotation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [4, 8, 16])
def test_match_recovers_hidden_rotation(B):
    f, g, true = planted_pair(B, seed=2)
    engine = CorrelationEngine(B, lane_width=2, tk=4)
    res = engine.match(f, g)
    errs = [ang_err(e, t) for e, t in zip(res.euler, true)]
    assert all(e < 1.5 * np.pi / B for e in errs), (B, errs, true, res)
    assert engine.stats["launches"] == 1
    assert engine.stats["padded_lanes"] == 1      # 1 request on 2 lanes


@pytest.mark.parametrize("N", [1, 3, 4])
def test_match_batch_lanes_are_independent(N):
    """Each lane of a packed launch answers ITS OWN request: batch results
    must equal N single-pair matches."""
    B = 8
    pairs = [planted_pair(B, seed=10 + n) for n in range(N)]
    engine = CorrelationEngine(B, lane_width=2, tk=4)
    results = engine.match_batch([p[0] for p in pairs],
                                 [p[1] for p in pairs])
    solo = CorrelationEngine(B, lane_width=1, tk=4)
    for n, (f, g, true) in enumerate(pairs):
        ref = solo.match(f, g)
        assert results[n].index == ref.index
        np.testing.assert_allclose(results[n].euler, ref.euler, atol=1e-9)
        np.testing.assert_allclose(results[n].peak, ref.peak, rtol=1e-9)
        errs = [ang_err(e, t) for e, t in zip(results[n].euler, true)]
        assert all(e < 1.5 * np.pi / B for e in errs)
    assert engine.stats["launches"] == (N + 1) // 2
    assert engine.stats["transforms"] == N


def test_match_bank_picks_planted_template():
    B = 8
    bank = [soft.random_s2_coeffs(B, seed=20 + i) for i in range(4)]
    true = hidden_rotation(4)
    query = s2.rotate_s2_coeffs(bank[2], true)
    engine = CorrelationEngine(B, lane_width=4, tk=4)
    best, results = engine.match_bank(query, bank)
    assert best == 2
    assert results[2].peak > 1.5 * max(r.peak for i, r in enumerate(results)
                                       if i != 2)
    assert engine.stats["launches"] == 1          # 4 templates, 4 lanes


def test_samples_enter_as_raw_grids():
    """Raw 2B x 2B samples route through s2_analysis and match the
    coefficient path exactly."""
    B = 8
    f, g, _ = planted_pair(B, seed=6)
    engine = CorrelationEngine(B, lane_width=1, tk=4)
    r_coeff = engine.match(f, g)
    r_samp = engine.match(s2.s2_synthesis(f), s2.s2_synthesis(g))
    assert r_samp.index == r_coeff.index
    np.testing.assert_allclose(r_samp.peak, r_coeff.peak, rtol=1e-9)


def test_refinement_is_subgrid():
    B = 8
    f, g, true = planted_pair(B, seed=2)
    engine = CorrelationEngine(B, lane_width=1, tk=4)
    coarse = engine.match(f, g, refine=False)
    fine = engine.match(f, g, refine=True)
    # same grid peak, offsets bounded by half a step per axis
    assert fine.index == coarse.index
    assert ang_err(fine.alpha, coarse.alpha) <= np.pi / (2 * B) + 1e-12
    assert ang_err(fine.gamma, coarse.gamma) <= np.pi / (2 * B) + 1e-12
    assert abs(fine.beta - coarse.beta) <= np.pi / (4 * B) + 1e-12
    # coarse estimate is exactly on the grid
    assert coarse.alpha in quadrature.alphas(B)
    errs = [ang_err(e, t) for e, t in zip(fine.euler, true)]
    assert all(e < 1.5 * np.pi / B for e in errs)


def test_match_rejects_bad_shapes():
    engine = CorrelationEngine(4, lane_width=1, tk=4)
    with pytest.raises(ValueError, match="expected S\\^2"):
        engine.match(np.zeros((3, 3)), soft.random_s2_coeffs(4))
    with pytest.raises(ValueError, match="queries"):
        engine.match_batch([soft.random_s2_coeffs(4)] * 2,
                           [soft.random_s2_coeffs(4)])


# ---------------------------------------------------------------------------
# structural: the iFSOFT really runs on fused batched lanes
# ---------------------------------------------------------------------------

def test_correlation_runs_fused_batched_lanes(monkeypatch):
    """One match_batch of 3 requests = ONE idwt_fused launch whose (K,
    C2, L) operand carries V*C*2 = 3*8*2 member rows."""
    calls = []
    orig = dwt_fused_mod.idwt_fused

    def spy(seeds, m, mp, cos_beta, lhs, l0s, **kw):
        calls.append(tuple(lhs.shape))
        return orig(seeds, m, mp, cos_beta, lhs, l0s, **kw)

    monkeypatch.setattr(dwt_fused_mod, "idwt_fused", spy)
    B, V = 8, 3
    engine = CorrelationEngine(B, lane_width=V, tk=4, impl="fused")
    pairs = [planted_pair(B, seed=30 + n) for n in range(V)]
    engine.match_batch([p[0] for p in pairs], [p[1] for p in pairs])
    assert len(calls) == 1                       # one launch for the batch
    assert calls[0][1] == V * 8 * 2              # V lanes x C=8 members x 2
    assert calls[0][2] == B                      # the degrees on the lanes
    assert engine.impl == "fused"


# ---------------------------------------------------------------------------
# service queue: packing, lane correctness, stats
# ---------------------------------------------------------------------------

def test_service_packs_concurrent_requests_into_one_launch():
    B = 8
    svc = SO3Service(bandwidths=(B,), lane_width=4, tk=4)
    svc.warmup()
    assert svc.stats()["launches"] == 0          # warmup launches excluded
    pairs = [planted_pair(B, seed=40 + n) for n in range(3)]
    futs = [svc.submit(f, g) for f, g, _ in pairs]
    served = svc.drain()
    assert served == 3
    st = svc.stats()
    assert st["launches"] == 1                   # >= 2 requests, ONE launch
    assert st["transforms"] == 3
    assert st["occupancy"] == pytest.approx(0.75)
    assert st["latency_s"]["p95"] > 0
    for fut, (f, g, true) in zip(futs, pairs):
        res = fut.result(timeout=0)
        errs = [ang_err(e, t) for e, t in zip(res.euler, true)]
        assert all(e < 1.5 * np.pi / B for e in errs)


def test_service_mixed_arrival_order_lands_in_correct_lanes():
    """Interleaved submissions across bandwidths: every future resolves to
    ITS OWN request's rotation (no lane cross-talk), same-B requests pack
    FIFO regardless of arrival interleaving."""
    svc = SO3Service(bandwidths=(4, 8), lane_width=2, tk=4)
    jobs, futs = [], []
    for n, B in enumerate([8, 4, 8, 4, 8]):      # mixed arrival order
        f, g, true = planted_pair(B, seed=50 + n)
        jobs.append((B, true))
        futs.append(svc.submit(f, g, refine=False))
    assert svc.drain() == 5
    st = svc.stats()
    # 3 requests at B=8 on 2-wide lanes -> 2 launches; 2 at B=4 -> 1
    assert st["engines"][8]["launches"] == 2
    assert st["engines"][4]["launches"] == 1
    assert st["launches"] == 3
    for fut, (B, true) in zip(futs, jobs):
        res = fut.result(timeout=0)
        errs = [ang_err(e, t) for e, t in zip(res.euler, true)]
        assert all(e < 1.5 * np.pi / B for e in errs), (B, errs)


def test_service_background_worker_smoke():
    B = 8
    svc = SO3Service(bandwidths=(B,), lane_width=2, tk=4, max_wait_ms=50.0)
    svc.warmup()
    svc.start()
    try:
        pairs = [planted_pair(B, seed=60 + n) for n in range(4)]
        futs = [svc.submit(f, g) for f, g, _ in pairs]
        results = [fut.result(timeout=120) for fut in futs]
    finally:
        svc.stop()
    for res, (_, _, true) in zip(results, pairs):
        errs = [ang_err(e, t) for e, t in zip(res.euler, true)]
        assert all(e < 1.5 * np.pi / B for e in errs)
    assert svc.stats()["completed"] == 4


def test_service_stop_without_drain_cancels_queued():
    """No Future is ever left unresolved: close(drain=False) settles every
    still-queued promise with a typed :class:`Cancelled` error -- a waiter
    already blocked in ``result()`` unblocks, it never hangs on a
    silently-dropped promise."""
    svc = SO3Service(bandwidths=(4,), lane_width=2, tk=4)
    f, g, _ = planted_pair(4, seed=70)
    fut = svc.submit(f, g)
    got = {}

    def waiter():
        try:
            got["res"] = fut.result(timeout=30)
        except BaseException as e:                # noqa: BLE001 - test probe
            got["exc"] = e

    th = threading.Thread(target=waiter)
    th.start()
    svc.stop(drain=False)
    th.join(timeout=30)
    assert not th.is_alive(), "waiter blocked forever on a dropped promise"
    exc = got.get("exc")
    assert isinstance(exc, Cancelled) and isinstance(exc, ServiceError)
    assert (exc.seq, exc.B) == (1, 4)            # shed carries identity
    st = svc.stats()
    assert st["queued"] == 0 and st["cancelled"] == 1
    assert st["resolved"] == st["submitted"] == 1
    # admission stays shut after close; the rejection is typed too
    with pytest.raises(Rejected, match="closed"):
        svc.submit(f, g).result(timeout=0)


def test_service_admission_rejects_when_queue_full():
    """Admission control: arrivals over max_queue resolve immediately with
    a typed Rejected error; accepted requests still serve to completion
    and the outcome ledger balances (submitted == resolved)."""
    svc = SO3Service(bandwidths=(4,), lane_width=2, tk=4, max_queue=2)
    f, g, _ = planted_pair(4, seed=71)
    futs = [svc.submit(f, g, refine=False) for _ in range(4)]
    shed = [fu for fu in futs if fu.done()]      # rejections settle at submit
    assert len(shed) == 2 and shed == futs[2:]   # FIFO admission
    for fu in shed:
        with pytest.raises(Rejected, match="queue full") as ei:
            fu.result(timeout=0)
        assert ei.value.B == 4
    assert svc.drain() == 2
    for fu in futs[:2]:
        assert fu.result(timeout=0).index is not None
    st = svc.stats()
    assert st["completed"] == 2 and st["rejected"] == 2 and st["shed"] == 2
    assert st["submitted"] == st["resolved"] == 4


def test_service_deadline_sheds_expired_requests():
    """A request still queued past its deadline is shed with a typed
    Expired error and never launched; undeadlined traffic is untouched."""
    svc = SO3Service(bandwidths=(4,), lane_width=2, tk=4)
    f, g, _ = planted_pair(4, seed=72)
    ok = svc.submit(f, g, refine=False)              # no deadline
    doomed = svc.submit(f, g, refine=False, deadline_s=0.01)
    time.sleep(0.05)
    assert svc.drain() == 1                          # sheds aren't "served"
    assert ok.result(timeout=0).index is not None
    with pytest.raises(Expired, match="deadline") as ei:
        doomed.result(timeout=0)
    assert ei.value.B == 4
    st = svc.stats()
    assert st["expired"] == 1 and st["completed"] == 1 and st["shed"] == 1
    assert st["submitted"] == st["resolved"] == 2


def test_service_retries_failed_launch_with_backoff(monkeypatch):
    """A transient launch failure requeues the group with backoff and the
    retry succeeds; the retry traffic lands in stats()."""
    svc = SO3Service(bandwidths=(4,), lane_width=2, tk=4,
                     max_retries=1, retry_backoff_s=0.01)
    eng = svc.engine(4)
    real = eng.correlation_grids
    calls = {"n": 0}

    def flaky(fs, gs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected transient launch failure")
        return real(fs, gs)

    monkeypatch.setattr(eng, "correlation_grids", flaky)
    f, g, true = planted_pair(4, seed=73)
    fut = svc.submit(f, g)
    assert svc.drain() == 2                  # two launch attempts, one request
    res = fut.result(timeout=0)
    errs = [ang_err(e, t) for e, t in zip(res.euler, true)]
    assert all(e < 1.5 * np.pi / 4 for e in errs)
    st = svc.stats()
    assert st["retries"] == 1 and st["completed"] == 1 and st["failed"] == 0
    assert calls["n"] == 2


def test_service_surfaces_launch_error_after_retries(monkeypatch):
    """Retries exhausted: the original launch error surfaces on the
    Future (typed 'failed' outcome), not a hang or a swallowed error."""
    svc = SO3Service(bandwidths=(4,), lane_width=2, tk=4,
                     max_retries=1, retry_backoff_s=0.005)
    eng = svc.engine(4)

    def broken(fs, gs):
        raise RuntimeError("injected permanent launch failure")

    monkeypatch.setattr(eng, "correlation_grids", broken)
    f, g, _ = planted_pair(4, seed=74)
    fut = svc.submit(f, g)
    svc.drain()
    with pytest.raises(RuntimeError, match="permanent"):
        fut.result(timeout=0)
    st = svc.stats()
    assert st["failed"] == 1 and st["retries"] == 1 and st["completed"] == 0
    assert st["submitted"] == st["resolved"] == 1


def test_warm_bandwidths_reports_plan_cache():
    """The plan-cache-aware scheduling hook: warm_bandwidths() reflects
    what repro.plan has memoized, so the scheduler can prefer bandwidths
    that dispatch without a plan build."""
    from repro import plan as plan_mod
    plan_mod.clear_cache()
    assert plan_mod.warm_bandwidths() == {}
    plan_mod.plan(4, tk=4)
    warm = plan_mod.warm_bandwidths()
    assert warm.get(4, 0) >= 1 and 16 not in warm
    svc = SO3Service(bandwidths=(4, 16), lane_width=2, tk=4)
    svc.engine(4)
    assert svc._warm(4) and not svc._warm(16)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_service_mixed_bandwidth_fuzz_bitwise_parity(seed):
    """Property-style fuzz (deterministic seed): a random interleaving of
    submissions across B in {4, 8, 16} resolves every future exactly
    once, each BITWISE-identical to direct unbatched execution of the
    same pair (lane packing must not perturb a single ulp), while
    stats() and the obs service.* counters stay monotone across rounds."""
    rng = np.random.default_rng(1000 + seed)
    Bs = (4, 8, 16)
    svc = SO3Service(bandwidths=Bs, lane_width=2, tk=4)
    ref = {B: CorrelationEngine(B, lane_width=1, tk=4) for B in Bs}
    mono: dict[str, int] = {}

    def check_counters_monotone():
        for name in ("service.completed", "service.rejected",
                     "service.expired", "service.cancelled"):
            v = obs.counter(name)
            assert v >= mono.get(name, 0), name
            mono[name] = v

    last: dict[str, int] = {}
    for _round in range(3):
        jobs = []
        for _ in range(int(rng.integers(3, 8))):
            B = int(rng.choice(Bs))
            f, g, _ = planted_pair(B, seed=int(rng.integers(0, 2 ** 31)))
            refine = bool(rng.integers(0, 2))
            jobs.append((B, f, g, refine, svc.submit(f, g, refine=refine)))
        assert svc.drain() == len(jobs)
        for B, f, g, refine, fut in jobs:
            got = fut.result(timeout=0)          # exactly-once: resolved now
            want = ref[B].match(f, g, refine=refine)
            assert result_key(got) == result_key(want), (B, refine)
        st = svc.stats()
        for k in ("submitted", "resolved", "completed", "launches",
                  "transforms"):
            assert st[k] >= last.get(k, 0), k
        last = st
        check_counters_monotone()
    assert last["submitted"] == last["resolved"] == last["completed"]
    assert last["shed"] == last["failed"] == 0


def test_infer_bandwidth():
    assert infer_bandwidth(np.zeros((8, 15))) == 8       # coeffs
    assert infer_bandwidth(np.zeros((16, 16))) == 8      # samples
    with pytest.raises(ValueError, match="bandwidth"):
        infer_bandwidth(np.zeros((5, 7)))


def test_peak_euler_on_synthetic_grid():
    """peak_euler finds a planted grid maximum and refines toward an
    off-grid peak."""
    B = 8
    n = 2 * B
    i0, j0, k0 = 5, 7, 11
    ii, jj, kk = np.meshgrid(np.arange(n), np.arange(n), np.arange(n),
                             indexing="ij")
    # smooth bump with a slight alpha-offset -> refinement moves alpha only
    di = (ii - i0 - 0.3 + n / 2) % n - n / 2     # circular alpha distance
    C = np.exp(-0.5 * (di ** 2 + (jj - j0) ** 2 + (kk - k0) ** 2))
    res = peak_euler(C, B, refine=True)
    assert res.index == (i0, j0, k0)
    assert res.alpha > quadrature.alphas(B)[i0]
    assert res.beta == pytest.approx(quadrature.betas(B)[j0])
