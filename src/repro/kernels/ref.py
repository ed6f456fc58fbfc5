"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the mathematical contract the corresponding kernel must
match (asserted across shape/dtype sweeps in tests/test_kernels.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["dwt_ref", "idwt_ref", "wigner_rec_table_ref", "attention_ref"]


def dwt_ref(d, rhs):
    """Clustered DWT: out[k, c, l] = sum_j d[k, l, j] rhs[k, c, j]."""
    return jnp.einsum("klj,kcj->kcl", d, rhs,
                      preferred_element_type=jnp.promote_types(d.dtype, jnp.float32))


def idwt_ref(d, lhs):
    """Clustered iDWT: g[k, c, j] = sum_l d[k, l, j] lhs[k, c, l]."""
    return jnp.einsum("klj,kcl->kcj", d, lhs,
                      preferred_element_type=jnp.promote_types(d.dtype, jnp.float32))


def wigner_rec_table_ref(seeds, m, mp, cos_beta, B):
    """Three-term Wigner-d recurrence (paper Eq. 2), vectorized over clusters.

    seeds: (K, J) d(m, m, m'; beta); m, mp: (K,) ints; cos_beta: (J,).
    Returns d[K, B, J] with zeros for l < m.  Mirrors
    core.wigner.wigner_d_fundamental but as a jnp program (same code path
    the fused kernel executes, so the kernel check isolates kernel bugs
    from recurrence-formulation differences).
    """
    K, J = seeds.shape
    mf = m.astype(seeds.dtype)
    mpf = mp.astype(seeds.dtype)
    cb = jnp.broadcast_to(cos_beta[None, :], (K, J)).astype(seeds.dtype)

    def step(carry, l):
        d_prev, d_cur = carry
        lf = l.astype(seeds.dtype)
        d_cur = jnp.where((m == l)[:, None], seeds, d_cur)
        lp1 = lf + 1.0
        den = jnp.sqrt(jnp.maximum((lp1**2 - mf**2) * (lp1**2 - mpf**2), 1.0))
        A = lp1 * (2.0 * lf + 1.0) / den
        safe_l = jnp.maximum(lf, 1.0)
        mu = jnp.where(lf > 0, mf * mpf / (safe_l * lp1), 0.0)
        C = jnp.where(lf > 0,
                      lp1 * jnp.sqrt(jnp.maximum((lf**2 - mf**2) * (lf**2 - mpf**2), 0.0))
                      / (safe_l * den), 0.0)
        d_next = A[:, None] * (cb - mu[:, None]) * d_cur - C[:, None] * d_prev
        active = (m <= l)[:, None]
        out_l = jnp.where(active, d_cur, 0.0)
        d_prev = jnp.where(active, d_cur, 0.0)
        d_cur = jnp.where(active, d_next, 0.0)
        return (d_prev, d_cur), out_l

    init = (jnp.zeros_like(seeds), jnp.zeros_like(seeds))
    _, rows = jax.lax.scan(step, init, jnp.arange(B))
    return jnp.swapaxes(rows, 0, 1)  # (K, B, J)


def attention_ref(q, k, v, *, causal=True, scale=None):
    """Multi-head attention oracle with GQA.

    q: (B, Hq, S, D); k, v: (B, Hkv, S, D) with Hq % Hkv == 0.
    f32 softmax regardless of input dtype; returns q.dtype.
    """
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / D**0.5
    kq = jnp.repeat(k, g, axis=1)
    vq = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kq.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = _softmax(s)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32)).astype(q.dtype)


def _softmax(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)
