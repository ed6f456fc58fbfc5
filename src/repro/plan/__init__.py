"""``repro.plan`` -- the plan-then-execute entry point of the framework.

The module itself is callable (FFTW-style):

    from repro import plan
    t = plan(16)                  # resolve schedule, build resources
    fhat = t.forward(f)           # execute many times

See :mod:`repro.plan.transform` for the full design notes.
"""
from __future__ import annotations

import sys
import types

from .transform import (AUTO_V_CANDIDATES, IMPLS, Schedule,  # noqa: F401
                        Transform, cache_stats, clear_cache,
                        dense_table_bytes_limit, plan, warm_bandwidths)

__all__ = ["plan", "Transform", "Schedule", "clear_cache", "cache_stats",
           "dense_table_bytes_limit", "warm_bandwidths",
           "IMPLS", "AUTO_V_CANDIDATES"]


class _CallableModule(types.ModuleType):
    """Lets ``repro.plan(B, ...)`` build a Transform directly while the
    module keeps exposing Transform/Schedule/etc. as attributes."""

    def __call__(self, *args, **kwargs):
        return plan(*args, **kwargs)


sys.modules[__name__].__class__ = _CallableModule
