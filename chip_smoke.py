#!/usr/bin/env python3
"""Smoke run of the SO(3) FFT on a TPU, through ``repro.plan`` and
``SO3Service`` in one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded path on a 4-chip mesh

One chip runs three phases:

  transform  plan(128, float32), the paper-scale rung: inverse, then
             forward, of random coefficients; the roundtrip error is held
             to kernels.autotune.FP32_ROUNDTRIP_BOUNDS[128].
  reference  plan(32, float32) forward against the f64 host reference
             core.soft.forward_soft, under REF_TOL.
  serve      SO3Service(bandwidths=(32, 64), float32) answers 16
             rotational-matching requests; every recovered rotation is
             checked against its hidden one.

``--four-chips`` runs only the sharded path and what it is compared with:
plan(128, float32) on a (4,) "data" mesh, forward and a two-chunk
inverse_batch through the pipelined executor, against the one-chip plan
in the same process, under FOUR_CHIP_TOL.

Each phase prints one line: its error and tolerance, its compile seconds
apart from its run seconds (smoke timings of one run, not benchmark
numbers; serve reports its warm-up, which holds plan builds, compiles
and one launch per bandwidth, in place of a compile time), and whether
its compiled programs hold a ``tpu_custom_call``,
i.e. a Pallas kernel compiled for the chip rather than interpreted.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``.  The
script exits non-zero, without that line, when JAX finds no TPU or any
phase fails.  Errors are max |out - ref| over max |ref| on the valid
coefficients.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

TRANSFORM_B = 128
REF_B = 32
# f32 kernels against the f64 host reference: 5.3e-6 to 6.0e-6 measured
# at B = 32 on the CPU (interpret mode, 3 seeds); the bound leaves room
# for the chip's own f32 rounding and still fails any real fault, which
# shows as an O(1) error.
REF_TOL = 1e-3
SERVE_BANDWIDTHS = (32, 64)
SERVE_REQUESTS = 16
# the sharded and the one-chip plan run the same f32 recurrence per
# cluster; they differ only in tiling, FFT slabs and summation order
FOUR_CHIP_TOL = 1e-3
KERNEL_MARK = "tpu_custom_call"


class PhaseError(RuntimeError):
    """A phase produced a wrong result or ran without a chip kernel."""


def rel_err(out, ref, mask=None) -> float:
    out, ref = np.asarray(out), np.asarray(ref)
    if mask is not None:
        out, ref = out[..., mask], ref[..., mask]
    return float(np.abs(out - ref).max() / np.abs(ref).max())


def compile_fn(fn, *args):
    """jit + compile ``fn`` for ``args``: (executable, seconds, kernel?)."""
    import jax

    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    return exe, time.perf_counter() - t0, KERNEL_MARK in exe.as_text()


def run_fn(exe, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    return out, time.perf_counter() - t0


def report(phase: str, rec: dict) -> None:
    """One line per phase; raises PhaseError when the phase failed."""
    print(f"phase={phase} " + " ".join(f"{k}={v}" for k, v in rec.items())
          + "  (times are smoke timings of one run, not a benchmark)",
          flush=True)
    if not rec["kernel"]:
        raise PhaseError(f"{phase}: no {KERNEL_MARK} in a compiled program")
    if not rec["error"] <= rec["tol"]:
        raise PhaseError(f"{phase}: error {rec['error']:.3e} over "
                         f"{rec['tol']:.1e}")


def _built(t):
    """Build a plan's kernel operands before any jit traces them."""
    t.dwt_fn, t.idwt_fn
    return t


def phase_transform(B: int = TRANSFORM_B, seed: int = 0) -> dict:
    import jax.numpy as jnp
    from repro import plan
    from repro.core import soft
    from repro.kernels.autotune import FP32_ROUNDTRIP_BOUNDS

    t = _built(plan(B, jnp.float32))
    fhat = jnp.asarray(soft.random_coeffs(B, seed, np.complex64))
    inv, c_inv, k_inv = compile_fn(t.inverse, fhat)
    f, r_inv = run_fn(inv, fhat)
    fwd, c_fwd, k_fwd = compile_fn(t.forward, f)
    back, r_fwd = run_fn(fwd, f)
    return dict(B=B, impl=t.impl, streaming=t.soft_plan.streaming,
                error=rel_err(back, fhat, soft.coeff_mask(B)),
                tol=FP32_ROUNDTRIP_BOUNDS[B],
                compile_s=round(c_inv + c_fwd, 3),
                run_s=round(r_inv + r_fwd, 4), kernel=k_inv and k_fwd)


def phase_reference(B: int = REF_B, seed: int = 1) -> dict:
    import jax.numpy as jnp
    from repro import plan
    from repro.core import soft

    t = _built(plan(B, jnp.float32))
    f64 = soft.inverse_soft(soft.random_coeffs(B, seed))    # host, f64
    ref = soft.forward_soft(f64, B)
    f = jnp.asarray(f64.astype(np.complex64))
    fwd, c_fwd, k_fwd = compile_fn(t.forward, f)
    out, r_fwd = run_fn(fwd, f)
    return dict(B=B, impl=t.impl,
                error=rel_err(out, ref, soft.coeff_mask(B)), tol=REF_TOL,
                compile_s=round(c_fwd, 3), run_s=round(r_fwd, 4),
                kernel=k_fwd)


def phase_serve(bandwidths=SERVE_BANDWIDTHS, n: int = SERVE_REQUESTS,
                seed: int = 0) -> dict:
    import jax.numpy as jnp
    from repro.core import soft
    from repro.so3 import SO3Service, angle_error, s2
    from repro.so3.correlate import random_rotation

    # no retries: a launch that fails surfaces on its Future at once
    svc = SO3Service(bandwidths=bandwidths, dtype=jnp.float32,
                     max_retries=0)
    warm = svc.warmup()
    kernel = True
    for B in bandwidths:                # the executor every launch runs
        t = svc.engine(B).transform
        T = jnp.zeros((t.V, B, 2 * B - 1, 2 * B - 1), jnp.complex64)
        kernel &= compile_fn(t.inverse_batch, T)[2]
    rng = np.random.default_rng(seed)
    jobs = []
    for r in range(n):
        B = bandwidths[r % len(bandwidths)]
        true = random_rotation(rng)
        g = soft.random_s2_coeffs(B, seed=seed + r)
        jobs.append((B, true, s2.rotate_s2_coeffs(g, true), g))
    t0 = time.perf_counter()
    futures = [svc.submit(f, g, bandwidth=B) for B, _, f, g in jobs]
    svc.drain()
    results = [fut.result(timeout=600) for fut in futures]
    run_s = time.perf_counter() - t0
    worst, recovered = 0.0, 0
    for (B, true, _, _), res in zip(jobs, results):
        errs = [angle_error(a, b) for a, b in
                zip((res.alpha, res.beta, res.gamma), true)]
        worst = max(worst, max(errs) * B / np.pi)   # in grid steps
        recovered += all(e < 1.5 * np.pi / B for e in errs)
    st = svc.stats()
    if st["failed"] or st["retries"] or recovered != n:
        raise PhaseError(f"serve: {recovered}/{n} rotations recovered, "
                         f"{st['failed']} failed, {st['retries']} retries")
    return dict(bandwidths=list(bandwidths), recovered=f"{recovered}/{n}",
                error=round(worst, 4), tol=1.5,
                warmup_s=round(sum(warm.values()), 3),
                run_s=round(run_s, 4), kernel=kernel)


def phase_four_chips(B: int = TRANSFORM_B, n_dev: int = 4,
                     seed: int = 2) -> dict:
    import jax
    import jax.numpy as jnp
    from repro import plan
    from repro.core import soft
    from repro.core.compat import make_mesh

    mesh = make_mesh((n_dev,), ("data",))
    tm = _built(plan(B, jnp.float32, mesh=mesh, axis=("data",)))
    t1 = _built(plan(B, jnp.float32))
    mask = soft.coeff_mask(B)
    f = jax.jit(t1.inverse)(
        jnp.asarray(soft.random_coeffs(B, seed, np.complex64)))
    fwd, c_fwd, k_fwd = compile_fn(tm.forward, f)
    got, r_fwd = run_fn(fwd, f)
    err = rel_err(got, jax.jit(t1.forward)(f), mask)
    # two lane-width chunks: the pipeline's prologue and one loop step
    n = 2 * tm.V
    fhats = jnp.asarray(np.stack([soft.random_coeffs(B, seed + 1 + i,
                                                     np.complex64)
                                  for i in range(n)]))
    inv, c_inv, k_inv = compile_fn(
        lambda x: tm.inverse_batch(x, overlap="pipelined"), fhats)
    grids, r_inv = run_fn(inv, fhats)
    err = max(err, rel_err(grids, t1.inverse_batch(fhats)))
    packed = tm.executor().forward(f)
    spans = {len(packed.sharding.device_set), len(grids.sharding.device_set)}
    if spans != {n_dev}:
        raise PhaseError(f"four_chips: sharded arrays span {spans} devices, "
                         f"expected {n_dev}")
    return dict(B=B, shards=n_dev, lanes=n, overlap=tm.schedule.overlap,
                devices_spanned=n_dev, error=err, tol=FOUR_CHIP_TOL,
                compile_s=round(c_fwd + c_inv, 3),
                run_s=round(r_fwd + r_inv, 4), kernel=k_fwd and k_inv)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-chip mesh and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 1
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing ({e})",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("transform", phase_transform),
                  ("reference", phase_reference), ("serve", phase_serve)]
    try:
        for name, fn in phases:
            report(name, fn())
    except Exception as e:          # any phase failing fails the run
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
