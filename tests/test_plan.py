"""Planner/executor layer (repro.plan): cache-key identity, roundtrips
for every selectable schedule, measured tuning, lane-packed batch
executors, sharded-vs-local equivalence (subprocess mesh), normalized
correlation scoring, and deprecation-shim parity with the old entry
points."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro import plan as plan_mod
from repro.core import batched, soft
from repro.kernels import ops
from repro.so3 import CorrelationEngine, s2
from repro.so3.correlate import random_rotation


MASKS = {B: soft.coeff_mask(B) for B in (4, 8, 16)}


def roundtrip_err(t, seed=0):
    fhat = soft.random_coeffs(t.B, seed)
    back = np.asarray(t.forward(t.inverse(fhat)))
    return np.abs(back - fhat)[MASKS[t.B]].max()


# ---------------------------------------------------------------------------
# cache identity: same config -> same Transform (and same resources)
# ---------------------------------------------------------------------------

def test_plan_cache_identity():
    a = plan_mod.plan(8, impl="fused", V=2, tk=4)
    before = plan_mod.cache_stats()
    b = plan_mod.plan(8, impl="fused", V=2, tk=4)
    after = plan_mod.cache_stats()
    assert a is b
    assert after["hits"] == before["hits"] + 1
    # resources built on the shared object are literally shared
    assert a.dwt_fn is b.dwt_fn and a.idwt_fn_batch is b.idwt_fn_batch
    assert a.soft_plan is b.soft_plan
    # a different config is a different Transform
    c = plan_mod.plan(8, impl="fused", V=4, tk=4)
    assert c is not a and c.V == 4


def test_plan_is_callable_module():
    """repro.plan(...) and repro.plan.plan(...) are the same entry."""
    assert plan_mod(8, impl="fused", V=2, tk=4) is \
        plan_mod.plan(8, impl="fused", V=2, tk=4)


def test_plan_rejects_bad_config():
    with pytest.raises(ValueError, match="impl"):
        plan_mod.plan(8, impl="nope")
    with pytest.raises(ValueError, match="V must"):
        plan_mod.plan(8, V=0)
    with pytest.raises(ValueError, match="tune"):
        plan_mod.plan(8, tune="sometimes")


def test_plan_refuses_64bit_compiled_kernels(monkeypatch):
    """Mosaic has no 64-bit types: a compiled-kernel plan in f64 fails in
    plan() with a clear error, before anything is built or compiled."""
    def no_build(*a, **k):
        raise AssertionError("plan() built a SoftPlan")

    monkeypatch.setattr(batched, "build_plan", no_build)
    with pytest.raises(ValueError, match="no 64-bit types"):
        plan_mod.plan(8, jnp.float64, interpret=False)
    monkeypatch.undo()
    # the pure-jnp reference and interpret-mode kernels keep f64
    assert plan_mod.plan(8, jnp.float64, impl="reference",
                         interpret=False).impl == "reference"
    assert plan_mod.plan(8, jnp.float64, interpret=True).impl == "fused"


def test_compiled_plans_follow_the_tpu_tiling():
    """Compiled kernels get l-chunks that are multiples of 128 (or B): the
    chunk of a (tk, C2, lchunk) block sits on the lanes.  Mesh plans get
    whole 8-row cluster tiles per device; interpret mode keeps small
    chunks and minimal padding."""
    with pytest.raises(ValueError, match="multiple of 128"):
        plan_mod.plan(16, jnp.float32, lchunk=8, interpret=False)
    assert plan_mod.plan(16, jnp.float32, lchunk=16,
                         interpret=False).schedule.lchunk == 16
    assert plan_mod.plan(16, jnp.float32, lchunk=4,
                         interpret=True).schedule.lchunk == 4
    from repro.core.compat import make_mesh
    mesh = make_mesh((1,), ("data",))
    t = plan_mod.plan(8, jnp.float32, mesh=mesh, axis=("data",),
                      interpret=False)
    assert t.soft_plan.n_padded % 8 == 0 and t.schedule.tk == 8


def test_plan_cache_keys_streaming_segments():
    """/L{lchunk}/P{precision} are part of the autotune cache key, and
    plans differing only in those knobs are distinct Transforms (the
    streaming half of the identity contract; parity lives in
    tests/test_streaming.py)."""
    from repro.kernels import autotune
    t = plan_mod.plan(8, impl="fused", V=2, tk=4)
    key = autotune._key(t.soft_plan, "fused", 2, 1 << 20,
                        lchunk=2, precision="bf16")
    assert key.endswith("/L2/Pbf16")
    assert autotune._key(t.soft_plan, "fused", 2, 1 << 20) \
        .endswith("/L0/Pfp32")
    s = plan_mod.plan(8, impl="fused", V=2, tk=4, lchunk=2)
    assert s is not t and s.schedule.lchunk == 2
    assert {"lchunk", "precision", "est_live_coeff_bytes",
            "est_peak_hbm_bytes"} <= t.describe().keys()


# ---------------------------------------------------------------------------
# SoftPlan cache: byte-bounded LRU with stats ($REPRO_PLAN_CACHE_BYTES)
# ---------------------------------------------------------------------------

def test_soft_plan_cache_byte_bound_and_stats(monkeypatch):
    """The SoftPlan cache evicts least-recently-used plans once the total
    exceeds $REPRO_PLAN_CACHE_BYTES -- exercised against a private cache
    so the shared process-wide cache (and the identity contracts other
    tests assert on it) is untouched."""
    import collections
    monkeypatch.delenv("REPRO_PLAN_CACHE_BYTES", raising=False)
    monkeypatch.setattr(batched, "_PLAN_CACHE", collections.OrderedDict())
    monkeypatch.setattr(batched, "_PLAN_CACHE_STATS",
                        {"hits": 0, "misses": 0, "evictions": 0})
    st = batched.plan_cache_stats()
    assert {"hits", "misses", "evictions", "plans", "bytes",
            "bytes_limit"} <= st.keys()
    assert st["plans"] == 0 and st["bytes"] == 0
    assert st["bytes_limit"] == batched._PLAN_CACHE_DEFAULT_BYTES

    a = batched.build_plan(8, dtype=jnp.float64)
    assert a is batched.build_plan(8, dtype=jnp.float64)       # hit
    st = batched.plan_cache_stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["plans"] == 1
    one_plan_bytes = st["bytes"]
    assert one_plan_bytes > 0

    # a limit that holds ~1.5 plans forces eviction on the third build
    monkeypatch.setenv("REPRO_PLAN_CACHE_BYTES", str(one_plan_bytes * 3 // 2))
    assert batched.plan_cache_stats()["bytes_limit"] == \
        one_plan_bytes * 3 // 2
    batched.build_plan(12, dtype=jnp.float64)
    st = batched.plan_cache_stats()
    assert st["evictions"] >= 1                   # LRU (B=8) was dropped
    assert st["bytes"] <= max(one_plan_bytes * 3 // 2,
                              max(n for _, n in
                                  batched._PLAN_CACHE.values()))
    b = batched.build_plan(8, dtype=jnp.float64)
    assert b is not a                             # evicted -> rebuilt
    # the most-recent entry always survives, even over-budget
    assert len(batched._PLAN_CACHE) >= 1
    # streaming plans are far smaller than dense ones in the same cache
    sp = batched.build_plan(8, dtype=jnp.float64, streaming=True)
    assert batched._PLAN_CACHE[
        (8, "<f8", None, None, True)][1] < one_plan_bytes


def test_cache_stats_surfaces_soft_plan_cache():
    st = plan_mod.cache_stats()
    assert "soft_plan_cache" in st
    assert {"hits", "misses", "evictions", "plans", "bytes",
            "bytes_limit"} <= st["soft_plan_cache"].keys()


# ---------------------------------------------------------------------------
# streaming resolution: explicit, auto-threshold, and describe() surfaces
# ---------------------------------------------------------------------------

def test_plan_streaming_resolution_and_describe(monkeypatch):
    from repro.kernels import autotune
    d = plan_mod.plan(8, impl="fused", V=2, tk=4).describe()
    assert d["streaming"] is False
    s = plan_mod.plan(8, impl="fused", V=2, tk=4, streaming=True).describe()
    assert s["streaming"] is True
    assert s["est_host_plan_bytes"] == autotune.estimate_host_plan_bytes(
        8, n_clusters=36, itemsize=8, streaming=True)
    assert s["est_host_plan_bytes"] < d["est_host_plan_bytes"]
    # the auto threshold: a tiny $REPRO_PLAN_DENSE_TABLE_BYTES makes the
    # planner stream even at B=8 without being asked
    assert plan_mod.dense_table_bytes_limit() == 512 * 1024 * 1024
    monkeypatch.setenv("REPRO_PLAN_DENSE_TABLE_BYTES", "1")
    assert plan_mod.dense_table_bytes_limit() == 1
    auto = plan_mod.plan(8, impl="fused", V=2, tk=8)   # fresh config
    assert auto.soft_plan.streaming
    # dense-only impls never auto-stream, whatever the threshold says
    ref = plan_mod.plan(8, impl="reference", V=1, tk=8)
    assert not ref.soft_plan.streaming


def test_precision_bounds_measured_vs_extrapolated():
    """B=128's bf16 bound is measured (benchmarks/error_table.py on
    streaming plans); only 256/512 remain extrapolated, and describe()
    warns when a bf16 schedule leans on an extrapolated bound."""
    import warnings
    from repro.kernels import autotune
    assert 128 not in autotune.PRECISION_BOUND_EXTRAPOLATED
    assert autotune.PRECISION_BOUND_EXTRAPOLATED == frozenset({256, 512})
    t16 = plan_mod.plan(16, dtype=jnp.float32, impl="fused", V=1, tk=4,
                        lchunk=4, precision="bf16", streaming=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # no warning at measured B
        d = t16.describe()
    assert d["precision_bound_extrapolated"] is False
    t256 = plan_mod.plan(256, dtype=jnp.float32, impl="fused", V=1, tk=8,
                         lchunk=64, precision="bf16", streaming=True)
    with pytest.warns(UserWarning, match="EXTRAPOLATED"):
        d = t256.describe()
    assert d["precision_bound_extrapolated"] is True
    assert d["streaming"] is True


# ---------------------------------------------------------------------------
# build smoke: the CI paper-scale program, at test scale
# ---------------------------------------------------------------------------

def test_build_smoke_program_small_b():
    prog = pathlib.Path(__file__).parent / "progs" / "build_smoke.py"
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(prog), "--bandwidth", "16", "--lchunk", "4",
         "--roundtrip", "--max-rss-bytes", str(8 * 1024 ** 3)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (
        f"build_smoke.py failed\n--- stdout ---\n{proc.stdout[-4000:]}"
        f"\n--- stderr ---\n{proc.stderr[-4000:]}")
    import json
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["B"] == 16 and row["streaming"]
    assert row["plan_build_s"] > 0
    # jax trace/compile machinery dominates the delta at small B (the
    # program allows dense/10 + a 256 MiB fixed overhead); the real
    # dense-vs-streaming separation is asserted by CI's B = 128 run.
    assert 0 <= row["build_rss_delta_bytes"] \
        < row["dense_table_bytes"] / 10 + 256 * 1024 ** 2
    assert row["roundtrip_rel_err"] is not None
    assert row["roundtrip_rel_err"] < 1e-4     # fp32 at B=16


# ---------------------------------------------------------------------------
# roundtrip for every schedule the planner can select
# ---------------------------------------------------------------------------

# each schedule the planner can select: the reference, the monolithic
# fused kernel, the streaming member at B/2-row chunks, and a window-built
# (d-free) plan
SCHEDULES = {"reference": dict(impl="reference"),
             "fused": dict(impl="fused"),
             "fused-lchunk": dict(impl="fused", lchunk="B/2"),
             "fused-streaming": dict(impl="fused", streaming=True)}


@pytest.mark.parametrize("B", [4, 8, 16])
@pytest.mark.parametrize("impl", list(SCHEDULES))
def test_roundtrip_every_impl(B, impl):
    kw = dict(SCHEDULES[impl])
    if kw.get("lchunk") == "B/2":
        kw["lchunk"] = B // 2
    t = plan_mod.plan(B, V=1, tk=4, **kw)
    assert plan_mod.IMPLS == ("reference", "fused")
    assert t.impl == kw["impl"]
    assert t.schedule.lchunk == kw.get("lchunk")
    assert t.soft_plan.streaming == kw.get("streaming", False)
    assert roundtrip_err(t, seed=B) < 1e-11


@pytest.mark.parametrize("removed", [
    dict(impl="dense"), dict(impl="ragged"), dict(impl="onthefly"),
    dict(tl=4), dict(tj=16), dict(n_buckets=4)],
    ids=["dense", "ragged", "onthefly", "tl", "tj", "n_buckets"])
def test_removed_schedules_and_tiles_are_refused(removed):
    """The dense, ragged and on-the-fly schedules and the tiles and
    buckets only they used are gone: naming them fails loudly."""
    if "impl" in removed:
        with pytest.raises(ValueError, match=r"\('reference', 'fused'\)"):
            plan_mod.plan(8, **removed)
    else:
        with pytest.raises(TypeError, match="unexpected keyword"):
            plan_mod.plan(8, **removed)


def test_plan_matches_dense_oracle_bitwise_tolerances():
    """The plan-selected fused V-lane path agrees with the dense oracle
    (the PR-2 acceptance contract, now routed through the planner)."""
    B = 8
    tf = plan_mod.plan(B, impl="fused", V=4, tk=4)
    tr = plan_mod.plan(B, impl="reference")
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, s))
                       for s in range(4)])
    f_fused = np.asarray(tf.inverse_batch(fhats))
    f_ref = np.asarray(tr.inverse_batch(fhats))
    np.testing.assert_allclose(f_fused, f_ref, rtol=1e-11, atol=1e-11)
    back = np.asarray(tf.forward_batch(jnp.asarray(f_fused)))
    np.testing.assert_allclose(back, np.asarray(fhats), rtol=1e-8,
                               atol=1e-9)


# ---------------------------------------------------------------------------
# schedule resolution: static VMEM guard + measured autotune
# ---------------------------------------------------------------------------

def test_static_auto_v_respects_vmem_budget():
    wide = plan_mod.plan(8, impl="fused")
    assert wide.V == max(plan_mod.AUTO_V_CANDIDATES)
    assert wide.schedule.vmem_bytes <= wide.schedule.vmem_limit
    # a budget that only admits the narrowest lane width degrades to V=1
    tight = plan_mod.plan(8, impl="fused",
                          vmem_budget=plan_mod.plan(
                              8, impl="fused", V=1).schedule.vmem_bytes)
    assert tight.V == 1 and tight.schedule.source == "static"
    with pytest.raises(ValueError, match="VMEM"):
        plan_mod.plan(8, impl="fused", vmem_budget=1)
    with pytest.raises(ValueError, match="VMEM"):
        plan_mod.plan(8, impl="fused", V=8, vmem_budget=1)


def test_measured_tune_resolves_via_autotune(tmp_path):
    cache = tmp_path / "autotune.json"
    t = plan_mod.plan(4, impl="fused", tune="measure", tune_reps=1,
                      tune_cache=cache)
    s = t.schedule
    assert s.source == "measured"
    assert s.V in plan_mod.AUTO_V_CANDIDATES
    assert s.per_transform_s > 0
    assert cache.exists()            # winners persisted for the next plan
    assert roundtrip_err(t, seed=2) < 1e-11


# ---------------------------------------------------------------------------
# batch executors: lane packing + stats accounting
# ---------------------------------------------------------------------------

def test_batch_executors_match_singles_and_count_lanes():
    B, V, n = 8, 2, 3
    t = plan_mod.plan(B, impl="fused", V=V, tk=4)
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, s))
                       for s in range(n)])
    t.reset_stats()
    fs = t.inverse_batch(fhats)
    assert t.stats == {"launches": 2, "transforms": 3, "padded_lanes": 1}
    for i in range(n):
        np.testing.assert_allclose(np.asarray(fs[i]),
                                   np.asarray(t.inverse(fhats[i])),
                                   rtol=1e-11, atol=1e-11)
    # external stats sink: a client's accounting doesn't touch the plan's
    sink = dict(launches=0, transforms=0, padded_lanes=0)
    before = dict(t.stats)
    t.forward_batch(fs, stats=sink)
    assert sink["launches"] == 2 and t.stats == before
    # empty batch short-circuits
    assert t.inverse_batch(jnp.zeros((0, B, 2 * B - 1, 2 * B - 1),
                                     t.cdtype)).shape[0] == 0


# ---------------------------------------------------------------------------
# engine integration: V flows from the plan; scores are normalized
# ---------------------------------------------------------------------------

def test_engine_lane_width_comes_from_plan():
    eng = CorrelationEngine(8)               # no hard-coded lane width
    assert eng.lane_width == eng.transform.V
    assert eng.transform.schedule.source in ("static", "measured")
    t = plan_mod.plan(8, impl="fused", V=2, tk=4)
    assert t.engine() is t.engine()          # cached on the Transform
    assert t.engine().lane_width == 2


def test_normalized_score_ranks_across_template_power():
    """A 50x louder mismatched template can out-peak the planted one, but
    the normalized score still picks the planted match (satellite: peaks
    comparable across templates of different power)."""
    B = 8
    true = random_rotation(3)
    g = soft.random_s2_coeffs(B, seed=80)
    loud = 50.0 * soft.random_s2_coeffs(B, seed=81)
    query = s2.rotate_s2_coeffs(g, true)
    eng = CorrelationEngine(B, lane_width=2, tk=4)
    best, results = eng.match_bank(query, [loud, g])
    assert results[0].peak > results[1].peak      # raw peak is fooled
    assert best == 1                              # the score is not
    assert results[1].score == pytest.approx(1.0, abs=0.1)
    assert results[0].score < 0.5
    assert results[1].rank_key == results[1].score


# ---------------------------------------------------------------------------
# deprecation shims: the old entry points match the plan path exactly
# ---------------------------------------------------------------------------

def test_shim_parity_with_old_entry_points():
    B = 8
    t = plan_mod.plan(B, impl="fused", V=2, tk=4)
    fhat = soft.random_coeffs(B, seed=9)
    # old layer-by-layer path with the identical configuration
    old_plan = batched.build_plan(B, dtype=jnp.float64, pad_to=4)
    assert old_plan is t.soft_plan               # one plan, every consumer
    idwt = ops.make_idwt_fn(old_plan, tk=4)
    dwt = ops.make_dwt_fn(old_plan, tk=4)
    f_old = np.asarray(batched.inverse_clustered(old_plan, fhat,
                                                 idwt_fn=idwt))
    np.testing.assert_array_equal(np.asarray(t.inverse(fhat)), f_old)
    back_old = np.asarray(batched.forward_clustered(
        old_plan, jnp.asarray(f_old), dwt_fn=dwt))
    np.testing.assert_array_equal(np.asarray(t.forward(f_old)), back_old)
    # old-style engine construction == plan-based engine
    f, g = s2.rotate_s2_coeffs(soft.random_s2_coeffs(B, 7),
                               random_rotation(7)), soft.random_s2_coeffs(B, 7)
    r_old = CorrelationEngine(B, lane_width=2, tk=4).match(f, g)
    r_new = plan_mod.plan(B, impl="fused", V=2, tk=4).correlate(f, g)
    assert r_old.index == r_new.index
    np.testing.assert_allclose(r_old.euler, r_new.euler, atol=1e-12)
    np.testing.assert_allclose(r_old.score, r_new.score, rtol=1e-12)


# ---------------------------------------------------------------------------
# sharded-vs-local equivalence on a 2-device CPU mesh (subprocess)
# ---------------------------------------------------------------------------

def test_sharded_plan_matches_local():
    prog = pathlib.Path(__file__).parent / "progs" / "dist_plan.py"
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, str(prog)], capture_output=True,
                          text=True, timeout=900, env=env)
    assert proc.returncode == 0, (
        f"dist_plan.py failed\n--- stdout ---\n{proc.stdout[-4000:]}"
        f"\n--- stderr ---\n{proc.stderr[-4000:]}")
    assert "DIST_PLAN_OK" in proc.stdout
