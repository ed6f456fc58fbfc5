"""Benchmark driver: one section per paper table/figure + framework extras.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--section NAME]

Sections:
  error_table      paper Table 1 (roundtrip error, f64 + f32 ladder)
  workbalance      paper Figs 2-4 analog (schedule speedup bounds)
  soft_runtime     measured 1-core runtime (sequential vs clustered)
  kernel_schedule  folded-attention grid savings
  plan             repro.plan planner: build time, cache hits, executors
  distributed      serial-loop vs lane-packed sharded batches, overlap
                   off vs pipelined rows (2-dev mesh)
  correlation      SO(3) rotational matching: bank + service on fused lanes
  lm_step          reduced-config LM train/decode step timings
  roofline         per-cell roofline terms from dry-run artifacts
  paper_scale      paper-scale forward+inverse ladder (streaming + bf16
                   schedules vs reference), seeds BENCH_paper_scale.json

With --emit-root-json, every section whose main() returns dict rows also
writes a BENCH_<section>.json artifact at the repo root in the shared
benchmarks.emit schema (rows tagged with git SHA + section); paper_scale
then APPENDS to its committed artifact (the cross-PR perf history) with
the schema-loss guard run against the pre-append baseline.

With --trace PATH, the run's repro.obs spans (plan builds, autotune
sweeps, executor chunks, service requests) are dumped as a Chrome-trace
JSON -- load it at chrome://tracing or ui.perfetto.dev.
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")


def lm_step(fast=False):
    """Reduced-config step timings across the assigned architectures."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models import lm

    archs = ("smollm-135m", "rwkv6-3b", "olmoe-1b-7b") if fast else \
        configs.ARCH_NAMES
    print("# lm_step (reduced configs, 1-core CPU)")
    print("arch,train_ms,decode_ms")
    rows = []
    for arch in archs:
        cfg = configs.reduced(arch)
        params = lm.init(cfg, jax.random.key(0))
        rng = np.random.default_rng(0)
        B, S = 2, 64
        batch = {"labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                    (B, S)), jnp.int32)}
        if cfg.embed_inputs:
            batch["embeds"] = jnp.asarray(
                rng.normal(size=(B, S, cfg.d_model)) * 0.02, jnp.float32)
        else:
            batch["tokens"] = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        if cfg.pos_type == "mrope":
            batch["positions"] = jnp.asarray(
                np.tile(np.arange(S, dtype=np.int32), (3, B, 1)))

        gfn = jax.jit(jax.grad(lambda p, b: lm.loss_fn(p, cfg, b)))
        gfn(params, batch)
        t0 = time.time()
        jax.block_until_ready(gfn(params, batch))
        t_train = (time.time() - t0) * 1e3

        states = lm.state_init(cfg, B, S)
        step_in = {k: (v[:, :1] if k != "positions" else v[:, :, :1])
                   for k, v in batch.items() if k != "labels"}
        dfn = jax.jit(lambda p, b, st: lm.decode_step(p, cfg, b, st,
                                                      jnp.int32(0)))
        dfn(params, step_in, states)
        t0 = time.time()
        jax.block_until_ready(dfn(params, step_in, states)[0])
        t_dec = (time.time() - t0) * 1e3
        print(f"{arch},{t_train:.1f},{t_dec:.1f}")
        rows.append((arch, t_train, t_dec))
    return rows


SECTIONS = ("error_table", "workbalance", "soft_runtime", "kernel_schedule",
            "plan", "distributed", "correlation",
            "serve_mixed", "lm_step", "roofline", "paper_scale")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--section", default=None, choices=SECTIONS)
    ap.add_argument("--artifacts", default="artifacts/dryrun")
    ap.add_argument("--emit-root-json", action="store_true",
                    help="write BENCH_<section>.json at the repo root for "
                         "sections that return rows (shared emit schema)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="dump the run's repro.obs spans as a Chrome-trace "
                         "JSON at PATH")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_enable_x64", True)  # error tables need f64
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    wanted = [args.section] if args.section else list(SECTIONS)
    t_all = time.time()
    for name in wanted:
        t0 = time.time()
        print(f"\n===== {name} =====")
        rows = None
        if name == "error_table":
            from benchmarks import error_table
            rows = error_table.main(fast=args.fast)
        elif name == "workbalance":
            from benchmarks import workbalance
            rows = workbalance.main(fast=args.fast)
        elif name == "soft_runtime":
            from benchmarks import soft_runtime
            rows = soft_runtime.main(fast=args.fast)
        elif name == "kernel_schedule":
            from benchmarks import kernel_schedule
            rows = kernel_schedule.main(fast=args.fast)
        elif name == "plan":
            from benchmarks import planner
            rows = planner.main(fast=args.fast)
        elif name == "distributed":
            from benchmarks import distributed
            rows = distributed.main(fast=args.fast)
        elif name == "correlation":
            from benchmarks import correlation
            rows = correlation.main(fast=args.fast)
        elif name == "serve_mixed":
            from benchmarks import serve_load
            rows = serve_load.main(fast=args.fast)
        elif name == "lm_step":
            rows = lm_step(fast=args.fast)
        elif name == "roofline":
            from benchmarks import roofline
            rows = roofline.main(args.artifacts)
        elif name == "paper_scale":
            from benchmarks import paper_scale
            if args.emit_root_json:
                # CI perf-history feed: append sha-tagged rows to the
                # committed artifact, schema-guarded against it
                from benchmarks import emit
                baseline = emit.REPO_ROOT / "BENCH_paper_scale.json"
                rows = paper_scale.main(fast=args.fast, append=True,
                                        check_against=baseline)
            else:
                rows = paper_scale.main(fast=args.fast)
        if args.emit_root_json and name != "paper_scale":
            # paper_scale emits its own artifact (plus structural checks)
            from benchmarks import emit
            tagged = emit.tag_rows(name, rows or [])
            if tagged:
                print(f"-> {emit.emit_root_json(name, rows)}")
            else:
                print(f"-> no dict rows from {name}; nothing emitted")
        print(f"[{name}: {time.time() - t0:.1f}s]")
    if args.trace:
        from repro import obs
        path = obs.get_recorder().dump_chrome_trace(args.trace)
        print(f"\nchrome trace -> {path} "
              f"({len(obs.get_recorder().events())} events)")
    print(f"\ntotal {time.time() - t_all:.1f}s")


if __name__ == "__main__":
    main()
