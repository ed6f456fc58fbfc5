"""Subprocess program: distributed SOFT on 8 fake devices vs single-device
clustered reference.  Run by tests/test_distributed.py; asserts internally."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np
import jax

from repro.core.compat import make_mesh

from repro.core import batched, parallel, soft

B = 8


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4), ("data", "model"))

    plan = batched.build_plan(B, pad_to=8)
    fhat = soft.random_coeffs(B, seed=7)

    # reference (single device, clustered path -- already validated against
    # the dense reference and the O(B^6) direct transforms)
    f_ref = np.asarray(batched.inverse_clustered(plan, fhat))
    back_ref = np.asarray(batched.forward_clustered(plan, f_ref))

    for axis in (("data", "model"), ("model",)):
        n = int(np.prod([mesh.shape[a] for a in axis]))
        plan_n = batched.build_plan(B, pad_to=n)
        packed = parallel.dense_to_packed(plan_n, fhat)
        f_dist = np.asarray(
            parallel.distributed_inverse(plan_n, packed, mesh, axis))
        np.testing.assert_allclose(f_dist, f_ref, rtol=1e-11, atol=1e-11,
                                   err_msg=f"inverse axis={axis}")
        packed_back = parallel.distributed_forward(plan_n, f_dist, mesh, axis)
        back = np.asarray(parallel.packed_to_dense(plan_n, packed_back))
        np.testing.assert_allclose(back, back_ref, rtol=1e-11, atol=1e-11,
                                   err_msg=f"forward axis={axis}")
        np.testing.assert_allclose(back, fhat, rtol=1e-9, atol=1e-11,
                                   err_msg=f"roundtrip axis={axis}")

    # packed <-> dense is a faithful bijection on valid cells
    rt = np.asarray(parallel.packed_to_dense(
        plan, parallel.dense_to_packed(plan, fhat)))
    np.testing.assert_array_equal(rt, fhat)

    # the shard-balanced cluster order distributes like the plain path
    n = 8
    order = batched.shard_balanced_order(
        np.asarray([m for m, _ in batched.clusters_mod.build_cluster_table(
            B).rep]), n)
    plan_b = batched.build_plan(B, pad_to=n, order=order)
    f_b = np.asarray(parallel.distributed_inverse(
        plan_b, parallel.dense_to_packed(plan_b, fhat), mesh,
        ("data", "model")))
    np.testing.assert_allclose(f_b, f_ref, rtol=1e-11, atol=1e-11)
    packed_bb = parallel.distributed_forward(plan_b, f_b, mesh,
                                             ("data", "model"))
    back_b = np.asarray(parallel.packed_to_dense(plan_b, packed_bb))
    np.testing.assert_allclose(back_b, fhat, rtol=1e-9, atol=1e-11,
                               err_msg="shard-balanced path")

    # fused (ragged + on-the-fly) distributed DWT: the shard_map runs with
    # NO Wigner-table shard at all -- seeds + recurrence replace plan.d
    fused_dwt = parallel.make_fused_local_dwt(plan_b, n)
    fused_idwt = parallel.make_fused_local_idwt(plan_b, n)
    assert not any(op is plan_b.d for op in fused_dwt.operands + \
                   fused_idwt.operands), "fused path must not carry d"
    f_f = np.asarray(parallel.distributed_inverse(
        plan_b, parallel.dense_to_packed(plan_b, fhat), mesh,
        ("data", "model"), local_idwt=fused_idwt))
    np.testing.assert_allclose(f_f, f_ref, rtol=1e-11, atol=1e-11,
                               err_msg="fused inverse")
    packed_f = parallel.distributed_forward(plan_b, f_f, mesh,
                                            ("data", "model"),
                                            local_dwt=fused_dwt)
    back_f = np.asarray(parallel.packed_to_dense(plan_b, packed_f))
    np.testing.assert_allclose(back_f, fhat, rtol=1e-9, atol=1e-11,
                               err_msg="fused path")
    print("DIST_SOFT_OK")


if __name__ == "__main__":
    main()
