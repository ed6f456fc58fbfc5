#!/usr/bin/env python3
"""Readings that set the limits of a cell's check, and the served cell's
capacity sweep; run on the chip, not by the benchmark's own runs.

    python3 bench/calibrate.py readings --workload W --seeds 1 2 ... \
        [--control] [--seconds S]
    python3 bench/calibrate.py sweep --workload match_b64.served \
        --rates 4 8 12 16 --seconds 15

``readings`` builds the cell's driver once and, for each seed, runs the
timed path on that seed's inputs (one step of the closed loop, or a
``--seconds`` window of the open loop) and prints every number the check
can compare, one JSON line per seed.  ``--control`` runs the
lower-precision control in the program's place: the program's own bf16
path for the ladder (``precision="bf16"``), the reference computed in
bfloat16 for the served cell.  ``sweep`` offers each rate in turn to one
warmed service, in increasing order, and prints the latency and whether
the backlog held; it stops at the first rate that did not hold.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.loader import Catalog  # noqa: E402


def readings(args) -> None:
    from bench.drivers import ladder, served

    cat = Catalog(ROOT)
    wl = cat.workload(args.workload)
    config = dict(cat.config(wl["config"]))
    mix = dict(cat.traffic(wl["traffic"]))
    if args.control and config["driver"] == "ladder":
        config["plan"] = dict(config.get("plan", {}), precision="bf16")
    harness.device_info(wl["chips"], require_chip=True)
    harness.enable_compile_cache()
    seeds = args.seeds
    if config["driver"] == "ladder":
        drv = ladder.Driver(config, mix, seeds[0])
        drv.setup()
        import jax
        for seed in seeds:
            fhat = ladder.coefficients(drv.B, seed, 0)
            x = jax.device_put(fhat)
            grid = drv.inverse(x)
            coeffs = drv.forward(grid)
            got = ladder.reference_readings(fhat, np.asarray(grid),
                                            np.asarray(coeffs))
            print(json.dumps({"seed": seed, "control": args.control, **got}),
                  flush=True)
        return
    if args.control:
        from repro.so3 import correlate

        correlate.CorrelationEngine.correlation_grids = served.control_grids
    drv = served.Driver(config, mix, seeds[0])
    drv.setup()
    for seed in seeds:             # one warmed service, each seed's own pool
        drv.seed = seed
        drv.pool = served.request_pool(drv.B, int(mix["pool"]), seed)
        win = drv.window(args.seconds)
        drv.gather()
        got = drv.readings()
        print(json.dumps({"seed": seed, "control": args.control,
                          "attempted": win["attempted"],
                          "failed": win["failed"], **got}), flush=True)
    drv.svc.close(drain=False)


def sweep(args) -> None:
    from bench.drivers import served

    cat = Catalog(ROOT)
    wl = cat.workload(args.workload)
    config = cat.config(wl["config"])
    mix = dict(cat.traffic(wl["traffic"]))
    harness.device_info(wl["chips"], require_chip=True)
    harness.enable_compile_cache()
    drv = served.Driver(config, mix, args.seed)
    t0 = time.perf_counter()
    drv.setup()
    print(f"setup {time.perf_counter() - t0:.3f} s", flush=True)
    for rate in args.rates:
        drv.mix["rate_per_s"] = rate
        win = drv.window(args.seconds)
        lat = np.asarray(win["latencies_s"]) * 1e3
        third = max(len(lat) // 3, 1)
        first, last = np.median(lat[:third]), np.median(lat[-third:])
        # held: the backlog drained within a second of the window's end and
        # the latency did not climb through the window
        held = (win["window_s"] <= args.seconds + 1.0 and not win["failed"]
                and last <= 2.0 * first)
        print(json.dumps({
            "rate_per_s": rate, "held": bool(held),
            "attempted": win["attempted"],
            "failed": win["failed"], "window_s": win["window_s"],
            "completed_per_s": len(lat) / win["window_s"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_third_p50_ms": float(first),
            "last_third_p50_ms": float(last),
            "launches": win["counters"]["launches"],
            "transforms": win["counters"]["transforms"]}), flush=True)
        if not held:
            break
    drv.svc.close(drain=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", type=int, nargs="+", required=True)
    r.add_argument("--control", action="store_true")
    r.add_argument("--seconds", type=float, default=5.0)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", type=float, nargs="+", required=True)
    s.add_argument("--seconds", type=float, default=15.0)
    s.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    {"readings": readings, "sweep": sweep}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
