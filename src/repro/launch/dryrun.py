import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import (device count locks on first init); the
# dry-run is the ONLY entry point that fakes the device count.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production mesh, record cost/memory/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out artifacts/dryrun

Per cell this produces artifacts/dryrun/<cell>.json with:
  flops / bytes accessed (compiled.cost_analysis, per-device program),
  per-device memory_analysis (args/outputs/temp/code),
  collective bytes by op (launch.hlo, while-loop trip counts applied),
  lower/compile wall time, model-FLOPs (6*N*D) reference.

Success of compile() for all cells on BOTH meshes is deliverable (e); the
JSON artifacts feed benchmarks/roofline.py (deliverable g).
"""
import argparse
import dataclasses
import json
import time
import traceback

import numpy as np
import jax

from repro.core.compat import cost_analysis_dict, make_mesh
import jax.numpy as jnp

from repro import configs
from repro.configs.base import shapes_for
from repro.launch import hlo as hlolib
from repro.launch import specs as speclib
from repro.launch.flops import analytic_flops
from repro.launch.mesh import make_ctx, make_production_mesh
from repro.models import lm
from repro.optim import OptConfig
from repro.train import TrainConfig, make_train_step

BIG_PARAM_THRESHOLD = 50e9   # adafactor above this (optimizer memory)


# ---------------------------------------------------------------------------
# step builders (one per shape kind)
# ---------------------------------------------------------------------------

ACT_BUDGET_GB = float(os.environ.get("REPRO_ACT_BUDGET_GB", "6.0"))
# per-device activation-carry budget -> microbatching (env-tunable: the
# accum-count <-> collective-traffic tradeoff is a §Perf iteration axis)


def _auto_microbatch(cfg, ctx, B, S):
    """Gradient-accumulation size keeping saved scan carries under budget.

    The layer scan saves its (B_mb_local, S, d) carry per group for the
    backward pass; choose the largest local microbatch whose total carry
    bytes fit ACT_BUDGET_GB.  Under nested (sqrt) remat only the outer
    carries persist, plus one inner segment's transient residuals
    (~3 carry-equivalents per inner group)."""
    import math
    ndp = math.prod(ctx.mesh.shape[a] for a in ctx.dp_axes)
    b_loc = max(B // ndp, 1)
    pat = len(cfg.block_pattern)
    G = cfg.num_layers // pat
    if cfg.remat == "nested" and G:
        gi = cfg.remat_inner or max(int(np.sqrt(G)), 1)
        while G % gi:
            gi -= 1
        carries = G // gi + 3 * gi
    else:
        carries = G
    per_seq = S * cfg.d_model * 2 * carries * pat  # bf16 carries
    mb = b_loc
    while mb > 1 and mb * per_seq > ACT_BUDGET_GB * 1e9:
        mb //= 2
    micro = b_loc // mb  # number of accumulation steps (for reporting)
    return (mb * ndp if micro > 1 else 0), micro


def build_train(cfg, ctx, shape, opt_name):
    B, S = shape.global_batch, shape.seq_len
    micro_b, n_acc = _auto_microbatch(cfg, ctx, B, S)
    tcfg = TrainConfig(opt=OptConfig(name=opt_name), microbatch=micro_b)
    p_shape, p_sh = speclib.params_specs(cfg, ctx)
    step_fn = make_train_step(cfg, tcfg, ctx, param_shardings=p_sh)
    o_shape, o_sh = speclib.opt_specs(cfg, ctx, tcfg.opt, p_shape)
    b_shape, b_sh = speclib.batch_specs(cfg, B, S, ctx, with_labels=True)
    step_sds = jax.ShapeDtypeStruct((), jnp.int32)

    fn = jax.jit(lambda p, o, b, s: step_fn(p, o, None, b, s),
                 in_shardings=(p_sh, o_sh, b_sh, None),
                 donate_argnums=(0, 1))
    return fn, (p_shape, o_shape, b_shape, step_sds)


def build_prefill(cfg, ctx, shape):
    B, S = shape.global_batch, shape.seq_len
    b_shape, b_sh = speclib.batch_specs(cfg, B, S, ctx, with_labels=False)
    p_shape, p_sh = speclib.params_specs(cfg, ctx)
    # pin output layouts: logits vocab-sharded, KV/recurrent states like the
    # decode inputs -- otherwise the partitioner may replicate the emitted
    # caches (measured 30 GB/device on gemma prefill_32k, §Perf)
    st_shape = jax.eval_shape(lambda: lm.state_init(cfg, B, S))
    st_sh = speclib.state_shardings(cfg, st_shape, ctx, B)
    logits_sh = speclib._ns(ctx, speclib._dp_or_none(ctx, B),
                            ctx.model_axis)

    fn = jax.jit(lambda p, b: lm.prefill(p, cfg, b, S, ctx),
                 in_shardings=(p_sh, b_sh),
                 out_shardings=(logits_sh, st_sh))
    return fn, (p_shape, b_shape)


def build_decode(cfg, ctx, shape):
    B, S = shape.global_batch, shape.seq_len
    p_shape, p_sh = speclib.params_specs(cfg, ctx)
    (b_shape, st_shape, pos), (b_sh, st_sh, pos_sh) = \
        speclib.decode_specs(cfg, B, S, ctx)
    logits_sh = speclib._ns(ctx, speclib._dp_or_none(ctx, B),
                            ctx.model_axis)

    fn = jax.jit(lambda p, b, st, q: lm.decode_step(p, cfg, b, st, q, ctx),
                 in_shardings=(p_sh, b_sh, st_sh, pos_sh),
                 out_shardings=(logits_sh, st_sh),
                 donate_argnums=(2,))
    return fn, (p_shape, b_shape, st_shape, pos)


def build_soft(soft_cfg, ctx, mesh, direction="forward"):
    from repro.core import parallel

    B = soft_cfg.bandwidth
    # shard over the largest mesh-axis suffix whose size divides the beta
    # axis (2B); leading axes (pod) replicate -- in production they batch
    # independent transforms (rotational-matching workloads).
    names = tuple(mesh.axis_names)
    axis = names
    while axis and (2 * B) % int(np.prod([mesh.shape[a] for a in axis])):
        axis = axis[1:]
    if not axis:
        raise ValueError(f"no mesh suffix divides beta axis {2 * B}")
    n = int(np.prod([mesh.shape[a] for a in axis]))
    plan = speclib.soft_plan_specs(B, n)
    plan_sh = speclib.soft_shardings(plan, ctx, axis)
    if direction == "forward":
        f_sds = jax.ShapeDtypeStruct((2 * B,) * 3, jnp.complex64)
        fn = jax.jit(lambda pl, f: parallel.distributed_forward(
            pl, f, mesh, axis))
        return fn, (plan, f_sds)
    packed = jax.ShapeDtypeStruct((plan.n_padded, B, 8), jnp.complex64)
    fn = jax.jit(lambda pl, x: parallel.distributed_inverse(
        pl, x, mesh, axis))
    return fn, (plan, packed)


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def analyze(lowered, compiled, t_lower, t_compile, extra):
    ca = cost_analysis_dict(compiled)
    ma = compiled.memory_analysis()
    coll = hlolib.collective_bytes(compiled.as_text())
    flops_dev = float(ca.get("flops", -1.0))
    flops_an = extra.get("flops_analytic_global", 0.0) / extra["devices"]
    out = {
        "flops_per_device": flops_dev,
        "flops_analytic_per_device": flops_an,
        # proportional loop correction for bytes (see launch/flops.py doc)
        "bytes_accessed_per_device": float(ca.get("bytes accessed", -1.0)),
        "bytes_corrected_per_device": (
            float(ca.get("bytes accessed", 0.0)) * flops_an / flops_dev
            if flops_dev > 0 and flops_an > flops_dev else
            float(ca.get("bytes accessed", -1.0))),
        "collectives": coll,
        "memory": {
            "argument_gb": ma.argument_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "alias_gb": ma.alias_size_in_bytes / 1e9,
            "code_gb": ma.generated_code_size_in_bytes / 1e9,
        },
        "lower_s": t_lower,
        "compile_s": t_compile,
    }
    out.update(extra)
    return out


def run_cell(arch, shape_name, multi_pod, opt_override=None, save_hlo=None,
             remat=None, mesh_shape=None):
    if mesh_shape:  # hillclimb override: same chips, different DP/TP split
        dims = tuple(int(x) for x in mesh_shape.split("x"))
        names = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, names)
        mesh_name = "pod" + mesh_shape
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ctx = make_ctx(mesh)

    if arch.startswith("soft_b"):
        soft_cfg = configs.SOFT_CONFIGS[arch]
        fn, args = build_soft(soft_cfg, ctx, mesh,
                              "forward" if shape_name == "forward"
                              else "inverse")
        extra = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": "soft", "bandwidth": soft_cfg.bandwidth,
                 "devices": mesh.size}
    else:
        cfg = configs.get(arch)
        if remat:
            cfg = dataclasses.replace(cfg, remat=remat)
        shape = {s.name: s for s in shapes_for(cfg)}[shape_name]
        n_params = lm.count_params(cfg)
        opt_name = opt_override or (
            "adafactor" if n_params > BIG_PARAM_THRESHOLD else "adamw")
        if shape.kind == "train":
            fn, args = build_train(cfg, ctx, shape, opt_name)
        elif shape.kind == "prefill":
            fn, args = build_prefill(cfg, ctx, shape)
        else:
            fn, args = build_decode(cfg, ctx, shape)
        extra_mb = {}
        if shape.kind == "train":
            mb, n_acc = _auto_microbatch(cfg, ctx, shape.global_batch,
                                         shape.seq_len)
            extra_mb = {"microbatch_global": mb, "grad_accum_steps": n_acc}
        extra = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": shape.kind, "devices": mesh.size,
                 "params": n_params, **extra_mb,
                 "active_params": lm.count_active_params(cfg),
                 "tokens": shape.global_batch * (shape.seq_len
                                                 if shape.kind != "decode"
                                                 else 1),
                 "seq_len": shape.seq_len,
                 "global_batch": shape.global_batch,
                 "opt": opt_name if shape.kind == "train" else None}

    t0 = time.time()
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    # loop-aware analytic FLOPs (cost_analysis counts while bodies once)
    extra["flops_analytic_global"] = float(
        analytic_flops(fn, *args, mesh_size=mesh.size))
    result = analyze(lowered, compiled, t_lower, t_compile, extra)
    print(f"[dryrun] {arch} {shape_name} {mesh_name}: "
          f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
          f"flops/dev {result['flops_analytic_per_device']:.3e} "
          f"(hlo {result['flops_per_device']:.3e}) "
          f"coll {result['collectives']['total']:.3e}B "
          f"temp {result['memory']['temp_gb']:.2f}GB")
    print("memory_analysis:", compiled.memory_analysis())
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())
    return result


def all_cells():
    cells = []
    for arch in configs.ARCH_NAMES:
        for s in shapes_for(configs.get(arch)):
            cells.append((arch, s.name))
    for name in ("soft_b128", "soft_b256", "soft_b512"):
        cells.append((name, "forward"))
        cells.append((name, "inverse"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--opt", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--mesh-shape", default=None,
                    help="e.g. 64x4 (data x model), hillclimb override")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape in cells:
        for multi in meshes:
            cell_id = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            out_path = os.path.join(args.out, cell_id + ".json")
            if os.path.exists(out_path):
                print(f"[dryrun] skip existing {cell_id}")
                continue
            try:
                res = run_cell(arch, shape, multi, args.opt, args.save_hlo,
                               args.remat, args.mesh_shape)
                with open(out_path, "w") as f:
                    json.dump(res, f, indent=1)
            except Exception as e:
                failures.append((cell_id, repr(e)))
                print(f"[dryrun] FAIL {cell_id}: {e}")
                traceback.print_exc()
                if not args.continue_on_error:
                    raise
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for cid, err in failures:
            print("  ", cid, err)
        raise SystemExit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    main()
