"""Per-kernel validation: every Pallas kernel body (run in interpret mode)
against its pure-jnp oracle, swept over shapes and dtypes, plus end-to-end
integration into the clustered SOFT transforms."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import batched, soft
from repro.kernels import folded_attention as fa
from repro.kernels import ops, ref


RNG = np.random.default_rng(0)


def rand(shape, dtype=np.float32, scale=1.0):
    return (RNG.normal(size=shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# the Wigner recurrence: oracle and fused-kernel precision
# ---------------------------------------------------------------------------

def test_wigner_rec_ref_matches_table():
    """The jnp recurrence oracle itself reproduces the host f64 table."""
    B = 12
    plan = batched.build_plan(B, dtype=jnp.float64)
    seeds, m, mp, cb = ops.onthefly_inputs(plan)
    tab = ref.wigner_rec_table_ref(seeds, m, mp, cb, B)
    np.testing.assert_allclose(tab, plan.d, rtol=1e-11, atol=1e-12)


def test_fused_f32_accuracy():
    """The fused kernel's f32 recurrence against the same kernel in f64:
    the documented f32 drift at B = 16 stays at the f32 rung."""
    B = 16
    plan64 = batched.build_plan(B, dtype=jnp.float64, pad_to=8)
    plan32 = batched.build_plan(B, dtype=jnp.float32, pad_to=8)
    K, J = plan32.n_padded, 2 * B
    rhs = rand((K, 16, J), np.float32, scale=0.1)
    out32 = ops.make_dwt_fn(plan32, tk=8)(plan32, rhs)
    out64 = ops.make_dwt_fn(plan64, tk=8)(plan64, rhs.astype(np.float64))
    err = np.abs(np.asarray(out32) - np.asarray(out64)).max()
    assert 0 < err < 5e-4, err


# ---------------------------------------------------------------------------
# integration: kernels inside the full transform
# ---------------------------------------------------------------------------

# the fused family's members at B = 8: the monolithic kernel and the
# streaming kernel at two l-chunks
MEMBERS = [pytest.param({}, id="monolithic"),
           pytest.param({"lchunk": 4}, id="streaming-4"),
           pytest.param({"lchunk": 2}, id="streaming-2")]


@pytest.mark.parametrize("member", MEMBERS)
def test_forward_clustered_with_kernel(member):
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=8)
    fhat = soft.random_coeffs(B, 11)
    f = batched.inverse_clustered(plan, fhat)
    back_ref = np.asarray(batched.forward_clustered(plan, f))
    dwt_fn = ops.make_dwt_fn(plan, tk=4, **member)
    back_kernel = np.asarray(batched.forward_clustered(plan, f, dwt_fn=dwt_fn))
    np.testing.assert_allclose(back_kernel, back_ref, rtol=1e-9, atol=1e-10)
    np.testing.assert_allclose(back_kernel, fhat, rtol=1e-8, atol=1e-9)


@pytest.mark.parametrize("member", MEMBERS)
def test_inverse_clustered_with_kernel(member):
    B = 8
    plan = batched.build_plan(B, dtype=jnp.float64, pad_to=8)
    fhat = soft.random_coeffs(B, 12)
    f_ref = np.asarray(batched.inverse_clustered(plan, fhat))
    idwt_fn = ops.make_idwt_fn(plan, tk=4, **member)
    f_kernel = np.asarray(batched.inverse_clustered(plan, fhat,
                                                    idwt_fn=idwt_fn))
    np.testing.assert_allclose(f_kernel, f_ref, rtol=1e-9, atol=1e-10)


# ---------------------------------------------------------------------------
# folded causal attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,bq", [(64, 16), (128, 32), (128, 64)])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (4, 1)])
def test_folded_attention_sweep(S, bq, Hq, Hkv):
    B, D = 2, 32
    q = rand((B, Hq, S, D)) * 0.5
    k = rand((B, Hkv, S, D)) * 0.5
    v = rand((B, Hkv, S, D))
    out = ops.attention(q, k, v, bq=bq, bk=bq)
    expect = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_folded_attention_dtypes(dtype):
    B, H, S, D = 1, 2, 64, 64
    q = jnp.asarray(rand((B, H, S, D)) * 0.3, dtype)
    k = jnp.asarray(rand((B, H, S, D)) * 0.3, dtype)
    v = jnp.asarray(rand((B, H, S, D)), dtype)
    out = ops.attention(q, k, v, bq=16, bk=16)
    assert out.dtype == dtype
    expect = ref.attention_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(out.astype(np.float32),
                               expect.astype(np.float32), rtol=tol, atol=tol)


def test_folded_equals_naive_schedule():
    """Both schedules produce identical outputs; folded uses ~half the
    grid slots (the paper-P3 win)."""
    B, H, S, D, bq = 1, 2, 128, 32, 16
    q, k, v = (rand((B, H, S, D)) for _ in range(3))
    out_f = ops.attention(q, k, v, bq=bq, bk=bq, schedule="folded")
    out_n = ops.attention(q, k, v, bq=bq, bk=bq, schedule="naive")
    np.testing.assert_allclose(out_f, out_n, rtol=1e-6, atol=1e-6)
    slots_f = fa.grid_slots(S, bq, "folded")
    slots_n = fa.grid_slots(S, bq, "naive")
    assert slots_f < 0.6 * slots_n, (slots_f, slots_n)


def test_folded_attention_rejects_odd_blocks():
    q = rand((1, 1, 48, 16))
    with pytest.raises(ValueError, match="even number of q-blocks"):
        ops.attention(q, q, q, bq=16, bk=16)
