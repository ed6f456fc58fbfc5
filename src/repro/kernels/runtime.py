"""Kernel runtime helpers shared by every Pallas wrapper in this package.

Lives below ops.py so the kernel modules themselves (dwt_fused.py,
streaming.py, peaks.py, folded_attention.py) can resolve their
`interpret=None` defaults without importing ops (which imports them
back).
"""
from __future__ import annotations

import numpy as np
import jax

__all__ = ["I0", "default_interpret", "resolve_interpret"]

# The int32 zero of BlockSpec index maps and kernel loop bounds.  A bare
# Python 0 becomes an i64 under jax_enable_x64, which Mosaic refuses.
I0 = np.int32(0)


def default_interpret() -> bool:
    """Pallas interpret mode unless running on real TPU hardware."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """`None` -> backend default; anything else passes through unchanged."""
    return default_interpret() if interpret is None else bool(interpret)
