"""SO(3) correlation service launcher: micro-batched rotational matching.

``PYTHONPATH=src python -m repro.launch.serve_so3 --bandwidth 8 \
      --requests 16 --lane-width 4``

Synthesizes a rotational-matching workload (random spherical templates,
hidden rotations), drives it through :class:`repro.so3.SO3Service` --
warmup, micro-batch packing into fused V-lane iFSOFT launches, latency /
throughput / occupancy stats -- and verifies every recovered rotation
against its hidden truth.  ``--threaded`` exercises the background worker
with jittered arrivals; the default drains synchronously (deterministic
packing).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bandwidth", type=int, nargs="+", default=[8],
                    help="bandwidth(s) served; requests cycle through them")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--lane-width", type=int, default=0,
                    help="packing width V; 0 (default) takes V per "
                         "bandwidth from the plan's autotune/VMEM-guard "
                         "resolution (repro.plan)")
    ap.add_argument("--tk", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--threaded", action="store_true",
                    help="background worker + jittered arrivals instead of "
                         "submit-all + drain")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission bound on total queued requests; over "
                         "it submits resolve with a typed Rejected error "
                         "(0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request queue-wait deadline; requests still "
                         "queued past it resolve with a typed Expired "
                         "error (0 = no deadline)")
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="shard the engines over the first N devices "
                         "(lane-packed sharded inverse; 0 = local plans; "
                         "on CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launch to fake N devices)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.core import soft
    from repro.launch.compile_cache import enable_compile_cache
    from repro.so3 import SO3Service, ServiceError, angle_error, s2
    from repro.so3.correlate import random_rotation

    enable_compile_cache()
    mesh = None
    if args.mesh_shards > 0:
        from repro.core.compat import make_mesh
        if jax.device_count() < args.mesh_shards:
            raise SystemExit(
                f"--mesh-shards {args.mesh_shards} needs at least that many "
                f"devices, found {jax.device_count()} (on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count="
                f"{args.mesh_shards})")
        mesh = make_mesh((args.mesh_shards,), ("data",))
        print(f"mesh: {args.mesh_shards} shards over axis 'data' "
              f"(lane-packed sharded inverse)")

    lane_width = args.lane_width if args.lane_width > 0 else None
    svc = SO3Service(bandwidths=args.bandwidth, dtype=jnp.float32,
                     lane_width=lane_width, tk=args.tk,
                     max_wait_ms=args.max_wait_ms, mesh=mesh,
                     axis=("data",),
                     max_queue=args.max_queue or None,
                     deadline_s=args.deadline_ms / 1e3 or None)
    warm = svc.warmup()
    for B, s in warm.items():
        eng = svc.engine(B)
        print(f"warmup B={B}: {s:.2f}s (plan + Wigner seeds + fused kernel "
              f"compile, V={eng.lane_width} "
              f"[{eng.transform.describe()['source']}])")

    rng = np.random.default_rng(args.seed)
    jobs = []
    for r in range(args.requests):
        B = args.bandwidth[r % len(args.bandwidth)]
        true = random_rotation(rng)
        g = soft.random_s2_coeffs(B, seed=args.seed + r)
        f = s2.rotate_s2_coeffs(g, true)
        jobs.append((B, true, f, g))

    t0 = time.perf_counter()
    if args.threaded:
        svc.start()
    futures = []
    for B, true, f, g in jobs:
        futures.append(svc.submit(f, g, bandwidth=B))
        if args.threaded:
            time.sleep(float(rng.uniform(0, args.max_wait_ms / 2e3)))
    if args.threaded:
        svc.stop(drain=True)
    else:
        svc.drain()
    results, shed = [], []
    for (B, true, _, _), fut in zip(jobs, futures):
        try:
            results.append(((B, true), fut.result(timeout=120)))
        except ServiceError as e:
            # admission/deadline shed: a typed resolution, not a failure
            shed.append((B, type(e).__name__, e.reason))
    wall = time.perf_counter() - t0

    worst = 0.0
    for (B, true), res in results:
        errs = (angle_error(res.alpha, true[0]),
                angle_error(res.beta, true[1]),
                angle_error(res.gamma, true[2]))
        worst = max(worst, max(errs) * B / np.pi)  # in grid-resolution units
        assert all(e < 1.5 * np.pi / B for e in errs), \
            f"rotation not recovered at B={B}: {errs}"

    st = svc.stats()
    lat = st.get("latency_s", {})
    print(f"served {st['completed']} requests in {wall:.2f}s "
          f"({st['completed'] / wall:.1f} req/s)")
    print(f"launches: {st['launches']}  packed transforms: "
          f"{st['transforms']}  lane occupancy: {st['occupancy']:.2f}")
    if st["shed"] or st["retries"]:
        print(f"shed: {st['shed']} (rejected {st['rejected']}, expired "
              f"{st['expired']})  retries: {st['retries']}")
        for B, kind, reason in shed[:5]:
            print(f"  {kind} at B={B}: {reason}")
    if lat:
        print(f"latency  mean {lat['mean'] * 1e3:.1f} ms  "
              f"p50 {lat['p50'] * 1e3:.1f} ms  p95 {lat['p95'] * 1e3:.1f} ms")
    print(f"worst recovery error: {worst:.3f} grid steps (pi/B units)")
    print("OK: all rotations recovered to grid resolution")
    return st


if __name__ == "__main__":
    main()
