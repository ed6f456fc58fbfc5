"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode, which every other test runs, never goes through the TPU's
compiler (Mosaic).  These tests compile the fused and streaming forward
and inverse kernels, and the bank query's peak search, for one chip of a
described ``v5e:2x2`` topology at paper-scale shapes (no chip is needed or
used) and assert that each compiled program holds its ``tpu_custom_call``.
They run under the suite's global x64, so they also guard the int32 index
maps and loop bounds.  Lane width V and cluster tile tk come from the
planner's static resolution, so its VMEM guard is checked against the real
compiler.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import plan
from repro.kernels import autotune, dwt_fused, peaks, streaming

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from a persistent
    # cache without one: keep the cache off while this module runs
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _schedule(B):
    """(K, tk, V) of the planner's static f32 schedule at bandwidth B."""
    t = plan(B, F32)
    assert t.impl == "fused" and t.schedule.lchunk is None
    return t.soft_plan.n_padded, t.schedule.tk, t.V


def _compile_hlo(fn, shapes, one_chip, **kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    exe = jax.jit(partial(fn, interpret=False, **kw)).lower(*args).compile()
    return exe.as_text()


def _operands(B, K, tk, V, direction):
    J, C2 = 2 * B, 16 * V
    x = (K, J, C2) if direction == "fwd" else (K, B, C2)
    return [((K, J), F32), ((K,), I32), ((K,), I32), ((J,), F32),
            (x, F32), ((K // tk,), I32)]


@pytest.mark.parametrize("direction", ["fwd", "inv"])
@pytest.mark.parametrize("B,lanes", [(128, "one"), (128, "plan"),
                                     (256, "plan"), (64, "served")])
def test_fused_kernel_compiles_for_v5e(one_chip, B, lanes, direction):
    """``served`` is the match service's launch: B = 64, 4 lanes (C2 =
    64)."""
    K, tk, V = _schedule(B)
    V = {"one": 1, "served": 4}.get(lanes, V)
    fn = dwt_fused.dwt_fused if direction == "fwd" else dwt_fused.idwt_fused
    hlo = _compile_hlo(fn, _operands(B, K, tk, V, direction), one_chip,
                       B=B, tk=tk)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("direction", ["fwd", "inv"])
def test_fused_kernel_compiles_with_short_panels(one_chip, direction):
    """A budget that leaves room for a 32-row Wigner panel only: the
    kernel closes four panels per grid step."""
    B, P = 128, 32
    K, tk, _ = _schedule(B)
    limit = autotune.estimate_vmem_bytes(L=B, J=2 * B, C2=16, tk=tk,
                                         panel=P)
    assert autotune.panel_depth(L=B, J=2 * B, C2=16, tk=tk,
                                limit=limit) == P
    fn = dwt_fused.dwt_fused if direction == "fwd" else dwt_fused.idwt_fused
    hlo = _compile_hlo(fn, _operands(B, K, tk, 1, direction), one_chip,
                       B=B, tk=tk, vmem_limit=limit)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("direction", ["fwd", "inv"])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_streaming_kernel_compiles_for_v5e(one_chip, precision, direction):
    B, lchunk = 128, 32
    K, tk, V = _schedule(B)
    wdt = jnp.bfloat16 if precision == "bf16" else F32
    fn = (streaming.dwt_streaming if direction == "fwd"
          else streaming.idwt_streaming)
    shapes = _operands(B, K, tk, V, direction) + [
        ((B // lchunk, 2, K, 2 * B), wdt)]
    hlo = _compile_hlo(fn, shapes, one_chip, B=B, tk=tk, lchunk=lchunk,
                       precision=precision)
    assert "tpu_custom_call" in hlo


def test_grid_peaks_compiles_for_v5e(one_chip):
    """The bank query's peak search: V = 8 lanes of B = 64 grids, read as
    (V, (2B)^3 / 128, 128) blocks with the int32 row iota."""
    B = 64
    _, _, V = _schedule(B)
    assert V == 8
    hlo = _compile_hlo(peaks.grid_peaks, [((V,) + (2 * B,) * 3, F32)],
                       one_chip)
    assert "tpu_custom_call" in hlo and "%grid_peaks" in hlo
