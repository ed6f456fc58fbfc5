"""Operations of a TPU trace picked by the name of their instruction.

A Pallas kernel's event is named after the jitted function around its
``pallas_call``, as in ``%grid_peaks.1 = (f32[8,1,128]...) custom-call(...)``
or ``%idwt_fused.1 = f32[...] custom-call(...)``.  Every Pallas kernel is a
``tpu_custom_call``, so where one program runs several kernels, as a bank
query's chunk does (the iDWT and the peak search), only the name tells
them apart.
"""
from __future__ import annotations

import re

_SUFFIX = re.compile(r"\.\d+$")


def instruction(name: str) -> str:
    """``%grid_peaks.1 = ...`` -> ``grid_peaks``.  Read left of `` = ``
    only, so an operation that reads a kernel's output is not taken for
    the kernel."""
    lhs = name.partition(" = ")[0]
    return _SUFFIX.sub("", lhs.strip().lstrip("%"))


def named(ops, names) -> list:
    """The operations (name, start, duration) whose instruction is one of
    ``names``."""
    return [op for op in ops if instruction(op[0]) in names]
