"""Distributed-layer units that need NO fake multi-device subprocess:
packed <-> dense coefficient-layout bijection, shard-layout invariants
(shard-balanced order, ShardMeta l0 schedules), and the mesh-resident
DistExecutor on a trivial 1-shard mesh (the shard_map machinery runs for
real; multi-device equivalence lives in tests/progs/dist_plan.py)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import batched, clusters as clusters_mod, parallel, soft
from repro.core.compat import make_mesh


def _balanced_plan(B, n_shards, pad_to=None):
    """Mirror of the planner's mesh path: minimal padding (pad_to =
    n_shards) and the pad-aware shard-balanced deal."""
    l_start = clusters_mod.build_cluster_table(B).rep[:, 0]
    pad_to = pad_to or n_shards
    n_padded = -(-len(l_start) // pad_to) * pad_to
    order = batched.shard_balanced_order(l_start, n_shards,
                                         n_padded=n_padded)
    return batched.build_plan(B, pad_to=pad_to, order=order)


# ---------------------------------------------------------------------------
# packed <-> dense layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [4, 8])
def test_packed_dense_roundtrip(B):
    plan = batched.build_plan(B, pad_to=4)
    fhat = soft.random_coeffs(B, seed=3)
    packed = parallel.dense_to_packed(plan, fhat)
    assert packed.shape == (plan.n_padded, B, plan.gather_m.shape[1])
    back = np.asarray(parallel.packed_to_dense(plan, packed))
    np.testing.assert_array_equal(back, fhat)
    # and the packed layout itself survives a dense round (bijection on
    # the cells the plan's scatter tables address)
    packed2 = parallel.dense_to_packed(
        plan, parallel.packed_to_dense(plan, packed))
    np.testing.assert_array_equal(np.asarray(packed2), np.asarray(packed))


def test_packed_dense_batch_wrappers_match_singles():
    B, n = 8, 3
    plan = batched.build_plan(B, pad_to=4)
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, seed=s))
                       for s in range(n)])
    packed = parallel.dense_to_packed_batch(plan, fhats)
    for i in range(n):
        np.testing.assert_array_equal(
            np.asarray(packed[i]),
            np.asarray(parallel.dense_to_packed(plan, fhats[i])))
    dense = parallel.packed_to_dense_batch(plan, packed)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(fhats))


# ---------------------------------------------------------------------------
# shard-layout invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_shard_balanced_layout_invariants(n_shards):
    """n_shards = 8 does not divide the 36 clusters at B = 8, so it
    exercises the pad-aware deal (pad rows land in the last hand)."""
    B = 8
    plan = _balanced_plan(B, n_shards)
    per_shard = batched.shard_lstart(plan, n_shards)
    assert per_shard.shape == (n_shards, plan.n_padded // n_shards)
    # minimal padding: fewer than n_shards zero rows
    assert plan.n_padded - plan.n_clusters < n_shards
    # (a) extent-sorted WITHIN each shard: ascending l-start rows, so
    # every local block supports the fused kernels' per-tile l-truncation
    for s in range(n_shards):
        assert (np.diff(per_shard[s]) >= 0).all(), f"shard {s} unsorted"
    # (b) work-balanced ACROSS shards: total contraction rows per shard
    # (sum of B - l_start) within one max-cluster-extent of each other.
    # Pad rows are contiguous at the global end, so only the LAST
    # shard(s) can hold them -- those trade work for padding by design
    # and are excluded from the strict bound (their work can only be
    # lower, never higher).
    kloc = plan.n_padded // n_shards
    n_full = n_shards - -(-(plan.n_padded - plan.n_clusters) // kloc)
    work = (B - per_shard).sum(axis=1)
    assert work.max() - work[:n_full].min() <= B
    assert work[n_full:].max(initial=0) <= work.max()


@pytest.mark.parametrize("tk", [1, 2, 3])
def test_shard_meta_l0s_safe_for_every_shard(tk):
    B, n_shards = 8, 2
    plan = _balanced_plan(B, n_shards)
    meta = parallel.fused_shard_meta(plan, n_shards, tk)
    kloc = plan.n_padded // n_shards
    assert meta.tk == tk and len(meta.l0s) == kloc // tk
    per_shard = batched.shard_lstart(plan, n_shards)
    # the replicated per-tile l0 schedule must truncate NO shard's rows:
    # l0s[t] <= min over shards of that tile's l-starts
    tile_mins = per_shard.reshape(n_shards, kloc // tk, tk).min(axis=(0, 2))
    assert (meta.l0s <= tile_mins).all()
    # memoized by (plan, n_shards, tk) identity
    assert parallel.fused_shard_meta(plan, n_shards, tk) is meta


def test_shard_meta_rejects_nondividing_tile():
    plan = _balanced_plan(8, 2)
    kloc = plan.n_padded // 2
    bad = kloc + 1
    with pytest.raises(ValueError, match="not divisible"):
        parallel.fused_shard_meta(plan, 2, bad)


# ---------------------------------------------------------------------------
# DistExecutor on a 1-shard mesh (real shard_map, no fake devices)
# ---------------------------------------------------------------------------

def test_dist_executor_single_shard_matches_local():
    B = 8
    mesh = make_mesh((1,), ("data",))
    plan = _balanced_plan(B, 1, pad_to=4)
    fhat = soft.random_coeffs(B, seed=5)

    ex = parallel.DistExecutor(plan, mesh, ("data",), lane_width=2)
    f_ref = np.asarray(batched.inverse_clustered(plan, fhat))
    f_ex = np.asarray(ex.inverse(parallel.dense_to_packed(plan, fhat)))
    np.testing.assert_allclose(f_ex, f_ref, rtol=1e-11, atol=1e-11)
    packed_back = ex.forward(f_ex)
    back = np.asarray(parallel.packed_to_dense(plan, packed_back))
    np.testing.assert_allclose(
        back, np.asarray(batched.forward_clustered(plan, jnp.asarray(f_ref))),
        rtol=1e-11, atol=1e-11)

    # lane-packed batch: 3 transforms on lane_width=2 -> 2 launches, the
    # partial chunk zero-padded; results match the per-item path
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, seed=s))
                       for s in range(3)])
    stats = dict(launches=0, transforms=0, padded_lanes=0)
    fb = np.asarray(ex.inverse_batch(
        parallel.dense_to_packed_batch(plan, fhats), stats=stats))
    assert stats == {"launches": 2, "transforms": 3, "padded_lanes": 1}
    for i in range(3):
        np.testing.assert_allclose(
            fb[i], np.asarray(batched.inverse_clustered(plan, fhats[i])),
            rtol=1e-11, atol=1e-11)


def test_dist_executor_memoized_and_validates():
    B = 8
    mesh = make_mesh((1,), ("data",))
    plan = _balanced_plan(B, 1, pad_to=4)
    assert parallel.dist_executor(plan, mesh, ("data",)) is \
        parallel.dist_executor(plan, mesh, ("data",))
    with pytest.raises(ValueError, match="lane_width"):
        parallel.DistExecutor(plan, mesh, ("data",), lane_width=0)
    # empty batches short-circuit with the right output shapes
    ex = parallel.dist_executor(plan, mesh, ("data",))
    C = plan.gather_m.shape[1]
    assert ex.forward_batch(np.zeros((0, 2 * B, 2 * B, 2 * B))).shape == \
        (0, plan.n_padded, B, C)
    assert ex.inverse_batch(np.zeros((0, plan.n_padded, B, C))).shape == \
        (0, 2 * B, 2 * B, 2 * B)


# ---------------------------------------------------------------------------
# double-buffered overlap pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_chunks", [1, 2, 3, 5])
def test_pipeline_two_slot_rotation(n_chunks):
    """The fori_loop-carried buffer rotates between exactly two slots:
    each step's staged collective writes the slot the NEXT step reads,
    never the slot the concurrent kernel launch is reading."""
    steps = parallel.pipeline_steps(n_chunks)
    slots = parallel.pipeline_slots(n_chunks)
    assert len(steps) == len(slots) == n_chunks + 1
    # prologue stages chunk 0, epilogue computes the last chunk
    assert steps[0] == (("collective", 0),) and slots[0] == (None, 0)
    assert steps[-1] == (("compute", n_chunks - 1),)
    assert slots[-1] == ((n_chunks - 1) % 2, None)
    for i, (step, (read, write)) in enumerate(
            list(zip(steps, slots))[1:-1], start=1):
        # interior step: collective for chunk i, compute for chunk i-1
        assert step == (("collective", i), ("compute", i - 1))
        # two-slot invariant: write slot is NOT the read slot, and chunk
        # c always lives in slot c % 2
        assert read == (i - 1) % 2 and write == i % 2 and read != write
    # every chunk's collective precedes its compute by exactly one step
    coll = {c: s for s, halves in enumerate(steps)
            for kind, c in halves if kind == "collective"}
    comp = {c: s for s, halves in enumerate(steps)
            for kind, c in halves if kind == "compute"}
    assert set(coll) == set(comp) == set(range(n_chunks))
    assert all(comp[c] == coll[c] + 1 for c in range(n_chunks))


def test_pipeline_steps_rejects_empty():
    with pytest.raises(ValueError, match="n_chunks"):
        parallel.pipeline_steps(0)
    with pytest.raises(ValueError, match="n_chunks"):
        parallel.pipeline_slots(0)


def test_overlap_mode_validation():
    plan = _balanced_plan(8, 1, pad_to=4)
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="overlap"):
        parallel.DistExecutor(plan, mesh, ("data",), overlap="always")
    ex = parallel.dist_executor(plan, mesh, ("data",))
    with pytest.raises(ValueError, match="overlap"):
        ex.inverse_batch(np.zeros((2, plan.n_padded, 8,
                                   plan.gather_m.shape[1])),
                         overlap="bogus")


def test_pipelined_batch_matches_serial_single_shard():
    """overlap="pipelined" is a SCHEDULE change, not an arithmetic one:
    on the 1-shard mesh the pipelined batch is bitwise equal to the
    serial per-chunk launches (multi-device parity lives in
    tests/progs/dist_plan.py), and the jitted pipeline body really is a
    fori_loop with the collective inside it."""
    import jax
    B = 8
    mesh = make_mesh((1,), ("data",))
    plan = _balanced_plan(B, 1, pad_to=4)
    ex = parallel.DistExecutor(plan, mesh, ("data",), lane_width=2,
                               overlap="pipelined")
    assert ex.overlap == "pipelined"
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, seed=s))
                       for s in range(5)])
    packed = parallel.dense_to_packed_batch(plan, fhats)

    stats = dict(launches=0, transforms=0, padded_lanes=0)
    pipe = np.asarray(ex.inverse_batch(packed, stats=stats))
    # launch accounting identical to the serial path: ceil(5/2) chunks
    assert stats == {"launches": 3, "transforms": 5, "padded_lanes": 1}
    off = np.asarray(ex.inverse_batch(packed, overlap="off"))
    np.testing.assert_array_equal(pipe, off)

    grids = jnp.asarray(off)
    np.testing.assert_array_equal(
        np.asarray(ex.forward_batch(grids)),            # default: pipelined
        np.asarray(ex.forward_batch(grids, overlap="off")))

    # structural: the pipelined callable lowers to a carried loop (scan
    # for the static trip count; while if jax keeps it symbolic) whose
    # body holds the all-to-all -- i.e. the interleaving of
    # pipeline_steps is what actually compiles.  3 chunks so the loop
    # body is not inlined away (fori_loop unrolls a trip count of 1).
    p = plan
    three = jnp.concatenate([packed, packed[:1]])      # 6 = 3 chunks of V=2
    jaxpr = str(jax.make_jaxpr(ex._inverse_pipe_call())(
        p.reflected, p.sign, p.sign, p.gather_m, p.gather_mp, p.parity,
        three.reshape(3, 2, *packed.shape[1:]), *ex._lid.operands))
    loop_body = jaxpr.split("scan[" if "scan[" in jaxpr else "while[", 1)[-1]
    assert ("scan[" in jaxpr or "while[" in jaxpr) and \
        "all_to_all" in loop_body


def test_autotune_overlap_key_segment():
    """The /O{mode} cache-key segment keeps overlapped and serial
    schedules apart (and the /S{n} mesh segment is still there)."""
    from repro.kernels import autotune
    plan = _balanced_plan(8, 2)
    limit = autotune.vmem_limit_bytes()
    k_off = autotune._key(plan, "fused", 2, limit, 2)
    k_pipe = autotune._key(plan, "fused", 2, limit, 2, overlap="pipelined")
    assert k_off.endswith("/S2/Ooff/L0/Pfp32")
    assert k_pipe.endswith("/S2/Opipelined/L0/Pfp32")
    assert k_off != k_pipe and k_off.rsplit("/O", 1)[0] == \
        k_pipe.rsplit("/O", 1)[0]
    # static heuristic: mesh plans pipeline, single-shard plans don't
    assert autotune.static_overlap(1) == "off"
    assert autotune.static_overlap(2) == "pipelined"


def test_transform_batch_stats_parity_across_overlap():
    """Transform.stats accounting is schedule-independent: the serial
    drain and the double-buffered pipeline count identical launches /
    transforms / padded lanes, and an external ``stats=`` sink absorbs
    the counts without touching the transform's own counters."""
    from repro import plan as plan_mod
    mesh = make_mesh((1,), ("data",))
    t = plan_mod.plan(8, impl="fused", V=2, tk=4, mesh=mesh, axis=("data",))
    t.reset_stats()
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(8, seed=s))
                       for s in range(5)])   # 5 lanes on V=2: 3 chunks, 1 pad
    sinks, outs = {}, {}
    for mode in ("off", "pipelined"):
        sink = dict(launches=0, transforms=0, padded_lanes=0)
        outs[mode] = np.asarray(t.inverse_batch(fhats, stats=sink,
                                                overlap=mode))
        sinks[mode] = sink
    assert sinks["off"] == sinks["pipelined"] == \
        {"launches": 3, "transforms": 5, "padded_lanes": 1}
    np.testing.assert_array_equal(outs["off"], outs["pipelined"])
    # the forward direction counts the same way
    grids = jnp.asarray(outs["off"])
    fwd = {}
    for mode in ("off", "pipelined"):
        sink = dict(launches=0, transforms=0, padded_lanes=0)
        t.forward_batch(grids, stats=sink, overlap=mode)
        fwd[mode] = sink
    assert fwd["off"] == fwd["pipelined"] == \
        {"launches": 3, "transforms": 5, "padded_lanes": 1}
    # external sinks took every count: the plan's own stats stayed zero
    assert t.stats == {"launches": 0, "transforms": 0, "padded_lanes": 0}
    # and without a sink the counts land on the transform itself
    t.inverse_batch(fhats[:2])
    assert t.stats == {"launches": 1, "transforms": 2, "padded_lanes": 0}
    t.reset_stats()
