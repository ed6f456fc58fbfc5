#!/usr/bin/env python3
"""Readings that set the limits of the bank cell's check; run on the chip,
not by the benchmark's own runs.

    python3 bench/calibrate_bank.py --seeds 1 2 ... [--control] \
        [--seconds S] [--workload bank_b64.query]

One engine is built and warmed once.  For each seed it loads that seed's
bank, runs a closed-loop window of ``--seconds`` over that seed's queries,
and prints every number the check compares, one JSON line per seed.
``--control`` runs the program's bf16 path (the plan's ``precision=
"bf16"``) in place of the f32 one.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.drivers import bank  # noqa: E402
from bench.loader import Catalog  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="bank_b64.query")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cat = Catalog(ROOT)
    wl = cat.workload(args.workload)
    config = dict(cat.config(wl["config"]))
    mix = cat.traffic(wl["traffic"])
    if args.control:
        config["plan"] = dict(config.get("plan", {}), precision="bf16")
    harness.device_info(wl["chips"], require_chip=True)
    harness.enable_compile_cache()
    drv = bank.Driver(config, mix, args.seeds[0])
    drv.setup()
    for seed in args.seeds:
        drv.seed = seed
        drv.templates = bank.template_bank(drv.B, drv.M, seed)
        drv.pool = bank.query_pool(drv.templates, int(mix["pool"]), seed)
        drv.order = bank.traffic.closed_order(mix, seed)
        drv.bank = drv.engine.load_bank(drv.templates)
        win = drv.window(args.seconds)
        print(json.dumps({"seed": seed, "control": args.control,
                          "queries": win["attempted"], **drv.readings()}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
