"""Hypothesis property tests on system invariants (beyond the unit suites):
transform algebra, cluster-table structure, bucketing exactness, MoE
dispatch conservation, optimizer-state geometry."""
import dataclasses

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # not in the container image
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import batched, clusters, indexing, soft


@settings(max_examples=8, deadline=None)
@given(st.integers(3, 12), st.integers(0, 10**6))
def test_transform_adjoint_property(B, seed):
    """<F f, g>_coeff == <f, F* g>_grid up to the quadrature weights: the
    forward transform with weights is (scaled) adjoint to synthesis --
    checked via roundtrip of a delta at a random valid (l, m, m')."""
    rng = np.random.default_rng(seed)
    l = int(rng.integers(0, B))
    m = int(rng.integers(-l, l + 1))
    mp = int(rng.integers(-l, l + 1))
    fhat = np.zeros((B, 2 * B - 1, 2 * B - 1), complex)
    fhat[l, m + B - 1, mp + B - 1] = 1.0 + 0.5j
    plan = batched.build_plan(B)
    back = np.asarray(batched.forward_clustered(
        plan, batched.inverse_clustered(plan, fhat)))
    np.testing.assert_allclose(back, fhat, rtol=1e-9, atol=1e-11)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 40))
def test_cluster_table_partitions_orders(B):
    """Every (m, m') order pair appears in exactly one cluster slot."""
    tab = clusters.build_cluster_table(B)
    used = tab.sign != 0
    pairs = set()
    for k in range(tab.n_clusters):
        for c in range(8):
            if used[k, c]:
                pairs.add((int(tab.member_m[k, c]), int(tab.member_mp[k, c])))
    assert len(pairs) == (2 * B - 1) ** 2
    assert int(used.sum()) == (2 * B - 1) ** 2  # no duplicates either


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 4), st.integers(8, 64))
def test_moe_dispatch_conserves_tokens(seed, top_k, T):
    """Every kept (token, slot) lands in exactly one expert buffer cell and
    combine weights stay normalized."""
    from repro.models.moe import _dispatch_indices, _route
    import numpy as np
    E = 8
    rng = np.random.default_rng(seed)
    router = jnp.asarray(rng.normal(size=(16, E)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    m = dataclasses.make_dataclass("M", ["top_k", "num_experts",
                                         "capacity_factor"])(top_k, E, 1.5)
    gates, ids, probs = _route(router, xt, m)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)
    C = max(int(np.ceil(T * top_k / E * 1.5)), 1)
    eid, pos, keep = _dispatch_indices(ids, E, C)
    eid, pos, keep = map(np.asarray, (eid, pos, keep))
    # kept slots occupy distinct (expert, position) cells within capacity
    cells = {(int(e), int(p)) for e, p, k in zip(eid, pos, keep) if k}
    assert len(cells) == int(keep.sum())
    assert all(p < C for _, p in cells)
    # position-in-expert is dense: positions for each expert = 0..n_e-1
    for e in range(E):
        ps = sorted(p for ee, p in cells if ee == e)
        assert ps == list(range(len(ps)))


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6))
def test_adamw_state_mirrors_params(seed):
    from repro.optim import OptConfig, init_opt
    rng = np.random.default_rng(seed)
    shapes = [(3,), (4, 5), (2, 3, 4)]
    params = {f"p{i}": jnp.asarray(rng.normal(size=s), jnp.bfloat16)
              for i, s in enumerate(shapes)}
    st_ = init_opt(OptConfig(name="adamw"), params)
    for k, p in params.items():
        assert st_["mu"][k].shape == p.shape
        assert st_["mu"][k].dtype == jnp.float32
        assert st_["master"][k].dtype == jnp.float32


def test_window_attention_equals_full_when_window_covers():
    """local_attn with window >= S must equal full causal attention."""
    from repro.models import attention
    from repro import configs
    cfg = configs.reduced("recurrentgemma-9b")
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    p = attention.attn_init(key, cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 40, cfg.d_model)) * 0.1, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    full = attention.attn_apply(p, x, cfg, pos, window=0)
    wind = attention.attn_apply(p, x, cfg, pos, window=4096)
    np.testing.assert_allclose(np.asarray(wind), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
