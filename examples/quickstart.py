"""Quickstart: the SO(3) FFT in five minutes.

    PYTHONPATH=src python examples/quickstart.py [--bandwidth 16]

Walks the public plan-then-execute API end to end: ``repro.plan(B)``
resolves the kernel schedule and builds every cached resource ONCE, the
returned Transform executes many times.  We synthesize a random
bandlimited function on the Euler grid (iFSOFT), analyze it back
(FSOFT), verify roundtrip error at paper-Table-1 magnitudes, then plan
the same transform on the fused Pallas kernels (interpret mode on CPU)
and check it agrees.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np
import jax

jax.config.update("jax_enable_x64", True)

from repro import plan
from repro.core import soft


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bandwidth", type=int, default=16)
    args = ap.parse_args()
    B = args.bandwidth

    print(f"== SO(3) FFT quickstart, bandwidth B={B} ==")
    print(f"coefficients: {soft.coeff_count(B)}   "
          f"grid: {2 * B}^3 = {(2 * B) ** 3} samples")

    # one plan call owns schedule + Wigner tables + cluster metadata
    t0 = time.time()
    t = plan(B, impl="reference")          # pure-jnp executors
    print(f"plan built in {time.time() - t0:.2f}s "
          f"({t.soft_plan.n_clusters} symmetry clusters, "
          f"schedule={t.describe()['impl']}, V={t.V})")

    fhat = soft.random_coeffs(B, seed=0)
    f = t.inverse(fhat)                    # iFSOFT
    back = t.forward(f)                    # FSOFT
    mask = soft.coeff_mask(B)
    err = np.abs(np.asarray(back) - fhat)[mask].max()
    print(f"roundtrip max abs error: {err:.2e}  (paper Table 1: ~1e-14)")
    assert err < 1e-12

    # same transform planned onto the fused Pallas kernels (the default
    # impl; interpret mode on CPU)
    tk = plan(B, V=1, tk=4)
    back_k = tk.forward(f)
    kerr = np.abs(np.asarray(back_k) - np.asarray(back)).max()
    print(f"pallas DWT kernel vs reference: {kerr:.2e}")
    assert kerr < 1e-12

    # the plan is memoized: a second identical call is free
    t0 = time.time()
    again = plan(B, V=1, tk=4)
    assert again is tk
    print(f"plan cache hit in {time.time() - t0 + 1e-6:.6f}s "
          f"(same Transform object, same compiled kernels)")
    print("OK")


if __name__ == "__main__":
    main()
