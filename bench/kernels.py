"""Which device operations of a trace are the DWT kernels, and how long
the device spent on them and on everything else.

On a TPU the trace names each operation by its HLO instruction, as in

  %idwt_fused.1 = f32[8256,256,16]{...} custom-call(%copy-done.12, ...),
      custom_call_target="tpu_custom_call", ...
  %fusion.3 = f32[8256,256,16]{...} fusion(%idwt_fused.1, %pad_clamp_fusion),
      kind=kCustom, calls=%fused_computation.3

A Pallas kernel is a ``tpu_custom_call``, and its instruction takes the
name of the jitted function around the ``pallas_call`` (``dwt_fused``,
``idwt_fused``, ``dwt_streaming``, ``idwt_streaming``).  On the paths the
benchmark drives every Pallas kernel is a DWT, so either mark finds them.
The name is read left of `` = `` only: ``%fusion.3`` above reads the
kernel's output and is no kernel.

Everything else the device runs in a roundtrip is XLA's part of the grid
stages (``repro.core.batched``): the FFTs, which the TPU compiles to
convolution fusions that carry no ``fft`` in their names, the gathers and
scatters between grid and clusters, and layout copies.
"""
from __future__ import annotations

import re

from bench import trace as tr

DWT_NAMES = ("dwt_fused", "idwt_fused", "dwt_streaming", "idwt_streaming")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r"\.\d+$")


def is_dwt(name: str) -> bool:
    lhs, _, rhs = name.partition(" = ")
    base = _SUFFIX.sub("", lhs.strip().lstrip("%"))
    # operands are instruction names, so the target can only be its own
    return base in DWT_NAMES or PALLAS_TARGET in rhs


def busy_seconds(ops) -> float:
    """Seconds in which at least one of ``ops`` ran (their union)."""
    return sum(b - a for a, b in tr.merge(ops)) * 1e-9


def dwt_seconds(ops) -> tuple[float, int]:
    """Device seconds of the DWT kernels, and how many ran."""
    sel = [op for op in ops if is_dwt(op[0])]
    return busy_seconds(sel), len(sel)


def grid_seconds(ops) -> tuple[float, int]:
    """Device seconds in which XLA's grid stages ran and no DWT kernel
    did, and how many of their operations ran."""
    n = sum(1 for op in ops if not is_dwt(op[0]))
    return busy_seconds(ops) - dwt_seconds(ops)[0], n
