"""ShapeDtypeStruct input specs + shardings for every (arch x shape) cell.

No device allocation happens here: params/optimizer/state trees come from
jax.eval_shape and inputs are ShapeDtypeStructs (the shannon/kernels
dry-run pattern).  Shardings follow DESIGN.md Sec. 6:

  batch axes over ("pod","data"); heads/ffn/vocab/experts over "model";
  params FSDP'd over the data axes (ZeRO-3); decode caches shard KV-heads
  over "model" when divisible, else the sequence axis (SP), else replicate;
  long_500k (batch=1) replicates batch and shards state sequence axes.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import layers, lm, sharding as shlib
from repro.optim import OptConfig, init_opt


def _ns(ctx, *spec):
    return NamedSharding(ctx.mesh, P(*spec))


def _dp_or_none(ctx, B):
    """Batch axis spec: data axes if they divide B, else replicated."""
    import math
    n = math.prod(ctx.mesh.shape[a] for a in ctx.dp_axes)
    return ctx.dp if B % n == 0 and B >= n else None


# ---------------------------------------------------------------------------
# batches
# ---------------------------------------------------------------------------

def batch_specs(cfg, B, S, ctx, *, with_labels):
    dp = _dp_or_none(ctx, B)
    specs, shards = {}, {}
    if cfg.embed_inputs:
        specs["embeds"] = jax.ShapeDtypeStruct(
            (B, S, cfg.d_model), layers.dtype_of(cfg.compute_dtype))
        shards["embeds"] = _ns(ctx, dp, None, None)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        shards["tokens"] = _ns(ctx, dp, None)
    if with_labels:
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        shards["labels"] = _ns(ctx, dp, None)
    if cfg.pos_type == "mrope":
        specs["positions"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
        shards["positions"] = _ns(ctx, None, dp, None)
    return specs, shards


# ---------------------------------------------------------------------------
# params / optimizer
# ---------------------------------------------------------------------------

def params_specs(cfg, ctx):
    shapes = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    return shapes, shlib.param_shardings(shapes, ctx)


def opt_specs(cfg, ctx, opt: OptConfig, params_shape):
    shapes = jax.eval_shape(lambda p: init_opt(opt, p), params_shape)
    # mu/nu/master/stats mirror param names -> same rules apply; scalars
    # (step) fall through to replicated.
    return shapes, shlib.param_shardings(shapes, ctx)


# ---------------------------------------------------------------------------
# decode states
# ---------------------------------------------------------------------------

def _first_divisible(ctx, dims, prefer):
    """Pick the first axis in `prefer` whose dim divides the model axis."""
    nm = ctx.mesh.shape[ctx.model_axis]
    for ax in prefer:
        if dims[ax] % nm == 0 and dims[ax] >= nm:
            return ax
    return None


def state_shardings(cfg, states_shape, ctx, B):
    """Decode-state shardings, keyed on leaf name + rank (handles both the
    scan-stacked (G, ...) group states and the unstacked tail states)."""
    dp = _dp_or_none(ctx, B)
    nm = ctx.mesh.shape[ctx.model_axis]
    mdl = ctx.model_axis

    def div(n):
        return n % nm == 0 and n >= nm

    def leaf_spec(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k)))
                 for k in path]
        name = names[-1]
        shape = leaf.shape
        if name in ("k", "v"):          # (B, L, Hkv, D) cache
            spec = [dp, None, None, None]
            Bs, L, Hkv, D = shape[-4:]
            if div(Hkv):
                spec[2] = mdl
            elif div(L):
                spec[1] = mdl            # sequence-parallel cache
        elif name == "S":                # (B, H, Dk, Dv) rwkv state
            spec = [dp, None, None, None]
            Bs, H, Dk, Dv = shape[-4:]
            if div(H):
                spec[1] = mdl
            # H not divisible: REPLICATE rather than shard Dk -- a sharded
            # scan carry forces a reshard every recurrence step (measured
            # 1.5 TB/dev of all-gathers on rwkv6 prefill_32k, §Perf)
        elif name == "conv":             # (B, W, d)
            spec = [dp, None, mdl if div(shape[-1]) else None]
        elif name in ("h", "x_prev"):    # (B, d)
            spec = [dp, mdl if div(shape[-1]) else None]
        else:
            spec = [None] * len(shape)
        pad = len(shape) - len(spec)     # leading scan-group axis
        return _ns(ctx, *([None] * pad), *spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, states_shape)


def decode_specs(cfg, B, S, ctx):
    """Specs for one serve_step: single new token against an S-long state."""
    batch, batch_sh = batch_specs(cfg, B, 1, ctx, with_labels=False)
    states = jax.eval_shape(lambda: lm.state_init(cfg, B, S))
    states_sh = state_shardings(cfg, states, ctx, B)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return (batch, states, pos), (batch_sh, states_sh, _ns(ctx))


# ---------------------------------------------------------------------------
# SOFT (the paper's own workload)
# ---------------------------------------------------------------------------

def soft_plan_specs(B, n_shards, dtype=jnp.float32):
    """ShapeDtype stand-in for a SoftPlan (no 0.4 TB table build)."""
    from repro.core import batched as b

    K = B * (B + 1) // 2
    Kp = ((K + n_shards - 1) // n_shards) * n_shards
    L, J, C = B, 2 * B, 8
    sds = jax.ShapeDtypeStruct
    leaves = dict(
        d=sds((Kp, L, J), dtype),
        gather_m=sds((Kp, C), jnp.int32), gather_mp=sds((Kp, C), jnp.int32),
        scatter_m=sds((Kp, C), jnp.int32), scatter_mp=sds((Kp, C), jnp.int32),
        sign=sds((Kp, C), dtype), reflected=sds((Kp, C), jnp.bool_),
        bin_src=sds((J * J,), jnp.int32),
        coeff_src=sds(((J - 1) ** 2,), jnp.int32),
        w=sds((J,), dtype), scale=sds((L,), dtype), parity=sds((L,), dtype),
    )
    return b.SoftPlan(B=B, table=None, n_padded=Kp, **leaves)


def soft_shardings(plan, ctx, axis):
    ax = axis if len(axis) > 1 else axis[0]
    return type(plan)(
        B=plan.B, table=None, n_padded=plan.n_padded,
        d=_ns(ctx, ax), gather_m=_ns(ctx), gather_mp=_ns(ctx),
        scatter_m=_ns(ctx), scatter_mp=_ns(ctx),
        sign=_ns(ctx), reflected=_ns(ctx, ax),
        bin_src=_ns(ctx), coeff_src=_ns(ctx),
        w=_ns(ctx, ax), scale=_ns(ctx), parity=_ns(ctx))
