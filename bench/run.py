#!/usr/bin/env python3
"""Runs one benchmark cell once on the chip and prints one JSON line.

    python3 bench/run.py --workload ladder_b128.single --seed 7 \
        --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The last line of
standard output is ``{"correct", "attempted", "failed", "metrics",
"device", ["breakdown"], "checks"}``; the checks (each number compared with
the reference, beside its limit) are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the
run exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

T_START = harness.process_start_perf()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
