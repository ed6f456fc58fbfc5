"""Closed loop over ``repro.plan``: each step is one inverse and then one
forward of a seeded coefficient set.

The configuration names the bandwidth and the plan's keywords; the mix
(``{"loop": "closed", "pool": n, "ahead_s": s}``) how many coefficient
sets the steps cycle through, and how many seconds of steps are
dispatched ahead of the one waited for, so that the chip stays fed while
the host stands still.  When the window's time is up nothing more is
sent, every step sent is waited for, and the clock is read after that
wait: all of that work counts, over all of that time.  Coefficients are the paper's (Sec. 4): Re and Im uniform
on [-1, 1] on every valid (l, m, m').  The check compares the inverse grid
and forward coefficients of one step, drawn from the seed among all the
window completed, with the f64 reference.
"""
from __future__ import annotations

import collections
import math
import sys
import time

import numpy as np

from bench import reference, traffic

KERNEL_MARK = "tpu_custom_call"
MAX_IN_FLIGHT = 64                  # bounds the outputs held at once


def coefficients(B: int, seed: int, i: int) -> np.ndarray:
    r = traffic.rng(seed, 100 + i)
    shape = (B, 2 * B - 1, 2 * B - 1)
    f = r.uniform(-1, 1, shape) + 1j * r.uniform(-1, 1, shape)
    return (f * reference.coeff_mask(B)).astype(np.complex64)


def rel_rms(out, ref) -> float:
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


def rel_max(out, ref) -> float:
    return float(np.abs(out - ref).max() / np.abs(ref).max())


def readings(fhat: np.ndarray, grid: np.ndarray, coeffs: np.ndarray,
             ref_grid: np.ndarray, ref_coeffs: np.ndarray) -> dict:
    """Every number the check may compare, for one step.

    ``grid`` is the program's inverse of ``fhat``, ``coeffs`` its forward of
    ``grid``; ``ref_grid`` is the reference inverse of ``fhat`` and
    ``ref_coeffs`` the reference forward of ``grid``, so ``ref_coeffs -
    fhat`` is the program's inverse error seen degree by degree.
    """
    B = fhat.shape[0]
    mask = reference.coeff_mask(B)
    c, rc = coeffs * mask, ref_coeffs * mask
    fwd_l = [rel_rms(c[l], rc[l]) for l in range(B)]
    inv_l = [rel_rms(rc[l], fhat[l]) for l in range(B)]
    return {
        "inverse_rms": rel_rms(grid, ref_grid),
        "inverse_max": rel_max(grid, ref_grid),
        "inverse_rms_median_degree": float(np.median(inv_l)),
        "inverse_rms_worst_degree": max(inv_l),
        "forward_rms": rel_rms(c, rc),
        "forward_max": rel_max(c, rc),
        "forward_rms_worst_degree": max(fwd_l),
        "forward_rms_median_degree": float(np.median(fwd_l)),
        "roundtrip_rms": rel_rms(c, fhat),
        "roundtrip_max": rel_max(c, fhat),
    }


def reference_readings(fhat, grid, coeffs) -> dict:
    ref_grid, ref_coeffs = reference.inverse_and_forward(
        fhat.astype(np.complex128), grid.astype(np.complex128))
    return readings(fhat, grid, coeffs, ref_grid, ref_coeffs)


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, log=sys.stderr):
        self.config, self.mix, self.seed, self.log = config, mix, seed, log
        self.B = int(config["B"])
        self.pool = [coefficients(self.B, seed, i)
                     for i in range(int(mix["pool"]))]
        self.order = traffic.closed_order(mix, seed)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro import plan

        t_plan = time.perf_counter()
        t = plan(self.B, jnp.float32, **self.config.get("plan", {}))
        # the plan builds its kernel operands lazily; built inside a trace
        # they would become arguments the compiled program is not given
        t.dwt_fn, t.idwt_fn
        self.x = [jax.device_put(f) for f in self.pool]
        t_compile = time.perf_counter()
        self.inverse = jax.jit(t.inverse).lower(self.x[0]).compile()
        grid = self.inverse(self.x[0])
        self.forward = jax.jit(t.forward).lower(grid).compile()
        t_warm = time.perf_counter()
        if jax.devices()[0].platform == "tpu":
            for exe in (self.inverse, self.forward):
                if KERNEL_MARK not in exe.as_text():
                    raise RuntimeError(f"a timed program holds no "
                                       f"{KERNEL_MARK}: no chip kernel")
        d = t.describe()
        print("plan: " + " ".join(f"{k}={d[k]}" for k in
                                  ("impl", "V", "lchunk", "precision",
                                   "streaming", "tk", "n_padded")),
              file=self.log, flush=True)
        for x in self.x:                     # first executions, untimed
            jax.block_until_ready(self.forward(self.inverse(x)))
        t0 = time.perf_counter()
        for x in self.x:
            jax.block_until_ready(self.forward(self.inverse(x)))
        t_step = (time.perf_counter() - t0) / len(self.x)
        self.depth = min(MAX_IN_FLIGHT, max(
            1, math.ceil(float(self.mix.get("ahead_s", 0)) / t_step)))
        print(f"steps in flight: {self.depth} ({t_step * 1e3:.1f} ms each)",
              file=self.log, flush=True)
        print(f"set-up: plan {t_compile - t_plan:.3f} s, compile "
              f"{t_warm - t_compile:.3f} s, warm-up "
              f"{time.perf_counter() - t_warm:.3f} s", file=self.log,
              flush=True)

    def step(self, i: int):
        """Dispatches step i; returns (pool index, grid, coefficients)."""
        k = int(self.order[i % len(self.order)])
        grid = self.inverse(self.x[k])
        return k, grid, self.forward(grid)

    def window(self, seconds: float) -> dict:
        import jax

        # one step of the window, uniform over all it completes, drawn from
        # the seed (reservoir sampling); only its outputs are kept
        pick = traffic.rng(self.seed, 4)
        sent, done = collections.deque(), 0

        def finish():
            nonlocal done
            out = sent.popleft()
            out[2].block_until_ready()
            if pick.random() * (done + 1) < 1.0:
                self.kept = out
            done += 1

        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.step"):
                sent.append(self.step(n))
            n += 1
            if len(sent) > self.depth:
                finish()
        while sent:
            finish()
        elapsed = time.perf_counter() - t0
        return {"window_s": elapsed, "steps": n, "attempted": n,
                "failed": 0}

    def collect(self) -> None:
        k, grid, coeffs = self.kept
        self.out = (k, np.asarray(grid), np.asarray(coeffs))
        del self.kept, self.x, self.inverse, self.forward

    def check(self) -> list[dict]:
        k, grid, coeffs = self.out
        got = reference_readings(self.pool[k], grid, coeffs)
        return [{"name": name, "value": got[name], "limit": limit}
                for name, limit in self.config["limits"].items()]
