"""The work a transform requires, whatever implements it, and the least
time a chip could take for it.

One direction of the SO(3) FFT's Wigner stage at bandwidth B contracts
each valid coefficient (B (4B^2 - 1) / 3 of them) against its 2B beta
samples: one complex-times-real multiply-add, 4 real operations, per pair.
The bytes are what crosses the stage's boundary: the (2B-1)^2 x 2B beta
slab and the coefficients, in complex64.  Generated d-rows, padded lanes
and padded clusters are not work, so an implementation that wastes them
reads lower, and a new kernel reads the same work as the old one.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
DIRECTIONS = ("forward", "inverse")
COMPLEX64_BYTES = 8


def coeff_count(B: int) -> int:
    return B * (4 * B * B - 1) // 3


def work(B: int, lanes: float, direction: str) -> tuple[float, float]:
    """(operations, bytes) of ``lanes`` transforms in one direction."""
    if direction not in DIRECTIONS:
        raise ValueError(f"direction must be one of {DIRECTIONS}")
    ops = 4.0 * coeff_count(B) * 2 * B * lanes
    nbytes = COMPLEX64_BYTES * ((2 * B - 1) ** 2 * 2 * B
                                + coeff_count(B)) * lanes
    return ops, nbytes


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip; unknown kinds are an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}")
    return table[device_kind]


def least_seconds(B: int, lanes: float, direction: str,
                  device_kind: str) -> tuple[float, str]:
    """Roofline time of the work, and which bound sets it."""
    ops, nbytes = work(B, lanes, direction)
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
