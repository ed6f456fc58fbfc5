"""Subprocess program: a mesh-planned Transform on 2 fake CPU devices
equals the local plan of the same configuration -- single transforms,
lane-packed sharded batches (one launch per V-chunk, no per-item loop),
per-mesh schedule resolution, and sharded correlation.  Run by
tests/test_plan.py; asserts internally."""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np
import jax
import jax.numpy as jnp

from repro import plan
from repro.core import parallel, soft
from repro.core.compat import make_mesh

B = 8


def check_single_transforms(mesh, t_local, fhat, mask):
    f_ref = np.asarray(t_local.inverse(fhat))
    back_ref = np.asarray(t_local.forward(f_ref))
    for impl in ("fused", "reference"):
        t_mesh = plan(B, impl=impl, mesh=mesh, axis=("data",))
        assert t_mesh.n_shards == 2
        assert t_mesh.schedule.n_shards == 2
        f_dist = np.asarray(t_mesh.inverse(fhat))
        np.testing.assert_allclose(f_dist, f_ref, rtol=1e-11, atol=1e-11,
                                   err_msg=f"inverse impl={impl}")
        back = np.asarray(t_mesh.forward(f_dist))
        np.testing.assert_allclose(back, back_ref, rtol=1e-11, atol=1e-11,
                                   err_msg=f"forward impl={impl}")
        np.testing.assert_allclose(back[mask], fhat[mask], rtol=1e-9,
                                   atol=1e-11,
                                   err_msg=f"roundtrip impl={impl}")
    return f_ref


def check_shared_resources(mesh):
    # the fused mesh plan shares ONE shard-metadata build between its
    # forward and inverse local kernels (PR-3 dedupe), ONE mesh-resident
    # executor serves every call, and no Wigner-table shard enters the
    # shard_map on the fused path
    t_f = plan(B, impl="fused", mesh=mesh, axis=("data",))
    meta = t_f.shard_meta()
    assert t_f._local_dwt().operands[0] is meta.seeds
    assert t_f._local_idwt().operands[0] is meta.seeds
    assert not any(op is t_f.soft_plan.d for op in
                   t_f._local_dwt().operands + t_f._local_idwt().operands)
    assert t_f.executor() is t_f.executor()
    assert t_f.executor().lane_width == t_f.V
    # auto-padding: the planner padded the cluster axis to the mesh size
    # (minimal: fewer than n_shards zero rows), so check_mesh_compat holds
    assert t_f.soft_plan.n_padded % 2 == 0
    assert t_f.soft_plan.n_padded - t_f.soft_plan.n_clusters < 2
    parallel.check_mesh_compat(t_f.soft_plan, 2)
    # describe() reports the mesh geometry and per-device resolution
    d = t_f.describe()
    assert d["mesh_axes"] == ["data"] and d["mesh_shape"] == [2]
    assert d["shard_clusters"] == t_f.soft_plan.n_padded // 2
    assert d["shard_beta"] == B
    assert d["lane_width"] == t_f.V
    return t_f


def check_lane_packed_batches(t_f, t_local, n=8):
    """Acceptance: a batch of 8 through the mesh plan matches the local
    plan within roundtrip tolerance while issuing LANE-PACKED sharded
    launches (ceil(n/V) launches, not n)."""
    fhats = np.stack([soft.random_coeffs(B, seed=100 + s) for s in range(n)])
    V = t_f.V
    expect_launches = -(-n // V)

    t_f.reset_stats()
    fb = np.asarray(t_f.inverse_batch(fhats))
    assert t_f.stats["launches"] == expect_launches, t_f.stats
    assert t_f.stats["transforms"] == n
    assert t_f.stats["padded_lanes"] == expect_launches * V - n
    f_singles = np.stack([np.asarray(t_local.inverse(fhats[i]))
                          for i in range(n)])
    np.testing.assert_allclose(fb, f_singles, rtol=1e-11, atol=1e-11,
                               err_msg="lane-packed sharded inverse_batch")

    t_f.reset_stats()
    bb = np.asarray(t_f.forward_batch(fb))
    assert t_f.stats["launches"] == expect_launches, t_f.stats
    back_singles = np.stack([np.asarray(t_local.forward(fb[i]))
                             for i in range(n)])
    np.testing.assert_allclose(bb, back_singles, rtol=1e-11, atol=1e-11,
                               err_msg="lane-packed sharded forward_batch")


def check_shim_parity(t_f, fhat):
    # the deprecated distributed_* shims execute on a memoized executor
    # and still match the plan path
    packed = parallel.dense_to_packed(t_f.soft_plan, fhat)
    f_shim = np.asarray(parallel.distributed_inverse(
        t_f.soft_plan, packed, t_f.mesh, ("data",)))
    np.testing.assert_allclose(f_shim, np.asarray(t_f.inverse(fhat)),
                               rtol=1e-11, atol=1e-11, err_msg="shim parity")
    assert parallel.dist_executor(t_f.soft_plan, t_f.mesh, ("data",)) is \
        parallel.dist_executor(t_f.soft_plan, t_f.mesh, ("data",))


def check_sharded_correlation(mesh):
    """match_bank on a mesh plan (template bank through the lane-packed
    sharded inverse) agrees with the local engine."""
    from repro.so3 import CorrelationEngine, s2
    from repro.so3.correlate import random_rotation

    true = random_rotation(21)
    g = soft.random_s2_coeffs(B, seed=90)
    decoys = [soft.random_s2_coeffs(B, seed=91 + i) for i in range(2)]
    query = s2.rotate_s2_coeffs(g, true)
    bank = decoys[:1] + [g] + decoys[1:]

    eng_local = CorrelationEngine(B, lane_width=2, tk=4)
    eng_mesh = plan(B, impl="fused", mesh=mesh, axis=("data",)).engine()
    best_l, res_l = eng_local.match_bank(query, bank)
    eng_mesh.reset_stats()
    best_m, res_m = eng_mesh.match_bank(query, bank)
    assert best_m == best_l == 1
    assert eng_mesh.stats["launches"] == -(-len(bank) // eng_mesh.lane_width)
    for rl, rm in zip(res_l, res_m):
        assert rl.index == rm.index
        np.testing.assert_allclose(rm.euler, rl.euler, atol=1e-9)
        np.testing.assert_allclose(rm.score, rl.score, rtol=1e-9)


def check_overlap_modes(mesh, batch_sizes=(8, 16)):
    """Acceptance (PR-5): overlap="pipelined" is bitwise equal to
    overlap="off" for forward and inverse batches on the 2-device mesh
    -- the double-buffered pipeline reorders the chunk schedule, not the
    arithmetic -- with identical launch/padding accounting, and the
    planner resolves mesh plans to the pipelined mode by default."""
    t = plan(B, impl="fused", mesh=mesh, axis=("data",))
    d = t.describe()
    assert d["overlap"] == "pipelined", d       # static mesh heuristic
    assert d["tune"] == "static" and d["source"] in ("static", "explicit")
    assert t.executor().overlap == "pipelined"
    # local plans have no collective to hide
    assert plan(B, impl="fused", tk=4).describe()["overlap"] == "off"
    # explicit override sticks (and is a distinct cached plan)
    t_off = plan(B, impl="fused", mesh=mesh, axis=("data",), overlap="off")
    assert t_off.describe()["overlap"] == "off" and t_off is not t

    ex = t.executor()
    V = t.V
    for n in batch_sizes:
        fhats = np.stack([soft.random_coeffs(B, seed=300 + s)
                          for s in range(n)])
        packed = parallel.dense_to_packed_batch(t.soft_plan, fhats)
        st_off = dict(launches=0, transforms=0, padded_lanes=0)
        st_pipe = dict(launches=0, transforms=0, padded_lanes=0)
        f_off = np.asarray(ex.inverse_batch(packed, overlap="off",
                                            stats=st_off))
        f_pipe = np.asarray(ex.inverse_batch(packed, overlap="pipelined",
                                             stats=st_pipe))
        np.testing.assert_array_equal(
            f_pipe, f_off, err_msg=f"pipelined inverse n={n} not bitwise")
        assert st_pipe == st_off == {
            "launches": -(-n // V), "transforms": n,
            "padded_lanes": -(-n // V) * V - n}, (st_off, st_pipe)
        b_off = np.asarray(ex.forward_batch(jnp.asarray(f_off),
                                            overlap="off"))
        b_pipe = np.asarray(ex.forward_batch(jnp.asarray(f_off),
                                             overlap="pipelined"))
        np.testing.assert_array_equal(
            b_pipe, b_off, err_msg=f"pipelined forward n={n} not bitwise")
    # the plan's own batch executors route through the pipelined default
    t.reset_stats()
    fhats = np.stack([soft.random_coeffs(B, seed=400 + s) for s in range(8)])
    fb = np.asarray(t.inverse_batch(fhats))
    assert t.stats["launches"] == -(-8 // V)
    f_off = np.asarray(t_off.inverse_batch(fhats))
    np.testing.assert_array_equal(fb, f_off,
                                  err_msg="plan-routed pipelined batch")


def check_mesh_schedule_resolution(mesh):
    # per-mesh measured tuning: the sweep runs on the per-device cluster
    # shard and the winner is cached under the mesh-shape key
    with tempfile.TemporaryDirectory() as tmp:
        cache = os.path.join(tmp, "autotune.json")
        t = plan(B, impl="fused", V=2, mesh=mesh, axis=("data",),
                 tune="measure", tune_reps=1, tune_cache=cache)
        s = t.schedule
        assert s.source == "measured" and s.n_shards == 2
        assert t.soft_plan.n_padded // 2 % s.tk == 0
        with open(cache) as fh:
            assert "/S2" in fh.read()
        fhat = soft.random_coeffs(B, seed=31)
        mask = soft.coeff_mask(B)
        back = np.asarray(t.forward(t.inverse(fhat)))
        np.testing.assert_allclose(back[mask], fhat[mask], rtol=1e-9,
                                   atol=1e-11, err_msg="measured mesh plan")
    # the planner cache counts mesh plans separately
    stats = plan.cache_stats()
    assert stats["mesh_misses"] >= 1 and stats["mesh_size"] >= 1
    assert stats["misses"] >= stats["mesh_misses"]


def main():
    assert jax.device_count() == 2, jax.device_count()
    mesh = make_mesh((2,), ("data",))
    fhat = soft.random_coeffs(B, seed=11)
    mask = soft.coeff_mask(B)

    t_local = plan(B, impl="fused", V=1, tk=4)
    check_single_transforms(mesh, t_local, fhat, mask)
    t_f = check_shared_resources(mesh)
    check_lane_packed_batches(t_f, t_local)
    check_shim_parity(t_f, fhat)
    check_sharded_correlation(mesh)
    check_overlap_modes(mesh)
    check_mesh_schedule_resolution(mesh)
    print("DIST_PLAN_OK")


if __name__ == "__main__":
    main()
