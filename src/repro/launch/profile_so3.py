"""End-to-end observability profile: one traced pass through the stack.

    PYTHONPATH=src python -m repro.launch.profile_so3 --bandwidth 8 \
        --trace trace.json --check

Clears the process :class:`repro.obs.Recorder`, then drives every
instrumented layer once -- a fresh ``tune="measure"`` plan build (the
autotune sweep times each candidate into the trace), a multi-chunk
batched forward/inverse (executor chunk spans), and a packed
:class:`repro.so3.SO3Service` workload (per-request spans + stage
spans) -- and writes the combined Chrome-trace JSON.  Load it at
chrome://tracing or https://ui.perfetto.dev.

``--check`` structurally validates the exported trace
(:func:`repro.obs.check_chrome_trace`: non-empty, monotonic begin
timestamps, and the plan-build / autotune-sweep / executor-chunk /
service-request spans all present) and exits 1 on failure -- the CI
obs-smoke step.  ``--bench`` additionally emits the recorder's
histogram/counter rows as BENCH_obs_profile.json in the shared
``benchmarks.emit`` schema (sha-tagged, perf-history compatible).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import tempfile
import time

REQUIRED_SPANS = ("plan.build", "plan.build.window", "plan.schedule",
                  "autotune.sweep", "autotune.candidate", "executor.chunk",
                  "service.pack", "service.launch", "service.refine",
                  "service.request")

# monotonic counters --check also requires (plan.host_peak_rss tracks the
# peak-RSS high-water deltas charged to plan construction)
REQUIRED_COUNTERS = ("plan.host_peak_rss",)


def _emit_rows(rows, out=None):
    """obs rows -> BENCH_obs_profile.json via benchmarks.emit (the
    benchmarks package lives at the repo root, not under src/)."""
    try:
        from benchmarks import emit
    except ImportError:
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
        from benchmarks import emit
    return emit.emit_root_json("obs_profile", rows, out=out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bandwidth", type=int, default=8)
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--lane-width", type=int, default=2,
                    help="service packing width V (also the traced plan's)")
    ap.add_argument("--trace", default="trace.json",
                    help="Chrome-trace JSON output path")
    ap.add_argument("--bench", action="store_true",
                    help="also emit BENCH_obs_profile.json (shared "
                         "benchmarks.emit schema) next to the repo root")
    ap.add_argument("--bench-out", default=None,
                    help="override the --bench output path")
    ap.add_argument("--check", action="store_true",
                    help="validate the exported trace structurally; "
                         "exit 1 on failure")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    import numpy as np
    from repro import obs, plan as plan_mod
    from repro.core import soft
    from repro.so3 import SO3Service

    B, V = args.bandwidth, args.lane_width
    rec = obs.get_recorder()
    rec.clear()                   # this trace covers exactly this run
    t_run = time.perf_counter()

    # 1. plan build with a measured sweep: a fresh tune cache forces the
    #    autotuner to actually time candidates into the trace
    plan_mod.clear_cache()
    with tempfile.TemporaryDirectory() as tmp:
        t = plan_mod.plan(B, dtype=jnp.float32, V=V, tune="measure",
                          tune_cache=os.path.join(tmp, "tune.json"))
    print(f"plan: B={B} V={t.describe()['V']} "
          f"[{t.describe()['source']}]")

    # 1b. streaming plan build: windowed construction (no dense d table)
    #     emits the plan.build.window span when the kernels stage their
    #     HBM window stacks, plus the plan.host_peak_rss counter
    #     (lchunk = B: one chunk, which every backend's tiling accepts)
    ts = plan_mod.plan(B, dtype=jnp.float32, impl="fused", V=1, lchunk=B,
                       streaming=True)
    ts.dwt_fn, ts.idwt_fn           # window stacks are built lazily
    print(f"streaming plan: B={B} lchunk={B} "
          f"d-free={ts.soft_plan.streaming}")

    # 2. batched executor traffic: 2V+1 lanes -> 3 chunks, one padded
    rng = np.random.default_rng(args.seed)
    n = 2 * V + 1
    f = (rng.normal(size=(n,) + (2 * B,) * 3)
         + 1j * rng.normal(size=(n,) + (2 * B,) * 3))
    fhat = t.forward_batch(f)
    t.inverse_batch(fhat)
    print(f"executor: {t.stats['launches']} chunked launches over "
          f"{n} lanes")

    # 3. service traffic: packed correlation requests
    svc = SO3Service(bandwidths=(B,), dtype=jnp.float32, lane_width=V)
    z = soft.random_s2_coeffs(B, seed=args.seed)
    futs = [svc.submit(z, z) for _ in range(args.requests)]
    svc.drain()
    for fut in futs:
        fut.result(timeout=120)
    st = svc.stats()
    lat = st.get("latency_s", {})
    print(f"service: {st['completed']} requests, "
          f"{st['launches']} launches, occupancy {st['occupancy']:.2f}, "
          f"p50 {lat.get('p50', 0) * 1e3:.1f} ms "
          f"p99 {lat.get('p99', 0) * 1e3:.1f} ms")

    wall = time.perf_counter() - t_run
    path = rec.dump_chrome_trace(args.trace)
    doc = rec.chrome_trace()
    print(f"trace -> {path} ({len(doc['traceEvents'])} events, "
          f"{wall:.2f}s wall)")
    print("span summary:")
    for name, q in rec.summary().items():
        print(f"  {name:<24} n={q['count']:<5} mean {q['mean'] * 1e3:8.2f} "
              f"ms  p95 {q['p95'] * 1e3:8.2f} ms")

    if args.bench:
        out = _emit_rows(rec.rows(), out=args.bench_out)
        print(f"bench rows -> {out}")

    if args.check:
        failures = obs.check_chrome_trace(doc, required_names=REQUIRED_SPANS)
        counters = rec.counters()
        for name in REQUIRED_COUNTERS:
            if name not in counters:
                failures.append(f"required counter missing: {name}")
        if failures:
            for msg in failures:
                print("FAIL:", msg)
            raise SystemExit(1)
        print(f"trace check: OK ({len(REQUIRED_SPANS)} required spans, "
              f"{len(REQUIRED_COUNTERS)} required counters, "
              f"monotonic timestamps)")
    return doc


if __name__ == "__main__":
    main()
