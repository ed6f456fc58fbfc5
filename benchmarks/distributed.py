"""Distributed executor smoke benchmark: serial-loop vs lane-packed
sharded batches, paired across overlap modes, on a 2-device mesh.

PR-3 left the sharded path serving batches serially (one shard_map
launch -- and one all-to-all -- PER transform); the mesh-resident
DistExecutor packs V transforms into the fused kernel's lane axis INSIDE
the shard_map (PR-4), and PR-5 adds the double-buffered overlap
pipeline: the ceil(n/V) V-chunks of a batch run through ONE fori_loop
shard_map call with chunk i+1's all-to-all staged while chunk i's local
kernel runs.  This section measures that contract on a faked 2-device
CPU mesh, emitting ONE row PER (B, overlap mode):

  * serial_s   -- n single sharded transforms through the same executor
                  (the old per-item behavior; shared baseline)
  * packed_s   -- one lane-packed batch of the same n under this row's
                  overlap mode ("off" = serial chunk launches,
                  "pipelined" = the double-buffered pipeline)
  * occupancy  -- packed transforms / (launches * V)
  * pipeline_* -- (pipelined rows) static schedule accounting from
                  core.parallel.pipeline_steps

Structural checks (CI smoke): both modes match the LOCAL plan at f64
magnitudes AND each other bitwise, launch accounting is ceil(n/V), the
packed paths beat the serial loop (the pipelined one is "no slower than
serial" -- interpret-mode CPU timing cannot show real collective
overlap, so the overlap gain itself is asserted STRUCTURALLY: every
interior pipeline step interleaves chunk i+1's collective with chunk
i's compute).  Rows are emitted as `JSON ` lines.

The real process re-execs itself in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=2 (per the dry-run
contract, only subprocesses fake device counts).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np


def run_child(fast=False):
    import jax
    import jax.numpy as jnp
    from repro import plan as plan_mod
    from repro.core import parallel
    from repro.core import soft
    from repro.core.compat import make_mesh

    assert jax.device_count() == 2, jax.device_count()
    mesh = make_mesh((2,), ("data",))
    bandwidths = (8,) if fast else (8, 16)
    rows = []
    for B in bandwidths:
        t = plan_mod.plan(B, impl="fused", mesh=mesh, axis=("data",))
        t_local = plan_mod.plan(B, impl="fused", tk=4)
        V = t.V
        n = 2 * V          # >= 2 chunks so the pipeline has steady state
        n_chunks = -(-n // V)
        fhats = jnp.stack([jnp.asarray(soft.random_coeffs(B, seed=s))
                           for s in range(n)])

        # warm every compiled shape (single lanes + both batch modes)
        jax.block_until_ready(t.inverse(fhats[0]))
        jax.block_until_ready(t.inverse_batch(fhats, overlap="off"))
        jax.block_until_ready(t.inverse_batch(fhats, overlap="pipelined"))

        t.reset_stats()
        t0 = time.perf_counter()
        f_serial = jnp.stack([t.inverse(f) for f in fhats])
        jax.block_until_ready(f_serial)
        serial_s = time.perf_counter() - t0
        launches_serial = t.stats["launches"]

        f_ref = np.stack([np.asarray(t_local.inverse(fhats[i]))
                          for i in range(n)])
        steps = parallel.pipeline_steps(n_chunks)
        interior = steps[1:-1]
        mode_results = {}
        for mode in ("off", "pipelined"):
            t.reset_stats()
            t0 = time.perf_counter()
            f_packed = t.inverse_batch(fhats, overlap=mode)
            jax.block_until_ready(f_packed)
            packed_s = time.perf_counter() - t0
            mode_results[mode] = np.asarray(f_packed)
            row = {
                "section": "distributed", "B": B, "impl": t.impl, "V": V,
                "overlap": mode, "n_shards": t.n_shards, "n": n,
                "schedule_overlap": t.schedule.overlap,
                "serial_s": serial_s, "packed_s": packed_s,
                "speedup": serial_s / packed_s,
                "launches_serial": launches_serial,
                "launches_packed": t.stats["launches"],
                "expected_launches": n_chunks,
                "occupancy": t.stats["transforms"]
                / (t.stats["launches"] * V),
                "max_abs_err": float(np.abs(np.asarray(f_packed)
                                            - f_ref).max()),
            }
            if mode == "pipelined":
                row.update({
                    "pipeline_steps": len(steps),
                    "pipeline_interleaved_steps": len(interior),
                    "pipeline_interleaved": all(
                        set(k for k, _ in s) == {"collective", "compute"}
                        and dict(s)["collective"] == dict(s)["compute"] + 1
                        for s in interior),
                    "bitwise_vs_off": bool(np.array_equal(
                        mode_results["pipelined"], mode_results["off"])),
                })
            rows.append(row)
    return rows


def check(rows) -> list[str]:
    failures = []
    for r in rows:
        tag = f"B={r['B']}/{r['overlap']}"
        if r["max_abs_err"] >= 1e-11:
            failures.append(f"{tag}: packed sharded batch off the local "
                            f"plan by {r['max_abs_err']:.2e}")
        if r["launches_packed"] != r["expected_launches"]:
            failures.append(f"{tag}: {r['launches_packed']} packed launches "
                            f"!= ceil(n/V) = {r['expected_launches']}")
        if r["launches_serial"] != r["n"]:
            failures.append(f"{tag}: serial baseline issued "
                            f"{r['launches_serial']} launches, not n")
        if r["packed_s"] >= r["serial_s"]:
            failures.append(f"{tag}: lane-packed batch ({r['packed_s']:.3f}s)"
                            f" did not beat the serial loop "
                            f"({r['serial_s']:.3f}s)")
        if r["overlap"] == "pipelined":
            if r["schedule_overlap"] != "pipelined":
                failures.append(f"{tag}: mesh plan did not resolve "
                                f"overlap=pipelined "
                                f"({r['schedule_overlap']!r})")
            if not r["pipeline_interleaved"]:
                failures.append(f"{tag}: pipeline schedule does not "
                                "interleave collective and compute steps")
            if r["pipeline_interleaved_steps"] < 1:
                failures.append(f"{tag}: no steady-state pipeline steps "
                                "(batch too shallow to overlap)")
            if not r["bitwise_vs_off"]:
                failures.append(f"{tag}: pipelined result is not bitwise "
                                "equal to the serial-chunk result")
    return failures


def child_main(fast=False):
    rows = run_child(fast=fast)
    print("# distributed: serial-loop vs lane-packed batches, "
          "overlap off vs pipelined (2 shards)")
    print("B,overlap,V,n,serial_s,packed_s,speedup,launches,occupancy,err")
    for r in rows:
        print(f"{r['B']},{r['overlap']},{r['V']},{r['n']},"
              f"{r['serial_s']:.4f},{r['packed_s']:.4f},"
              f"{r['speedup']:.2f},{r['launches_packed']},"
              f"{r['occupancy']:.2f},{r['max_abs_err']:.2e}")
    for r in rows:
        print("JSON " + json.dumps(r))
    failures = check(rows)
    for msg in failures:
        print("CHECK FAILED:", msg)
    if failures:
        raise SystemExit(1)
    print("CHECKS OK: both overlap modes match the local plan (and each "
          "other bitwise), issue ceil(n/V) lane-packed launches, beat the "
          "serial loop, and the pipelined schedule interleaves every "
          "interior collective with the previous chunk's compute")


def main(fast=False):
    """Re-exec in a subprocess with 2 fake CPU devices (the parent
    process may already hold a single-device jax, or the chip).  The
    child runs on the CPU backend, so its rows are host times and
    launch/collective counts, never device numbers."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_ENABLE_X64", "1")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.distributed", "--child"]
    if fast:
        cmd.append("--fast")
    proc = subprocess.run(cmd, env=env, text=True, capture_output=True,
                          timeout=1800)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise SystemExit(proc.returncode)


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.path.insert(0, "src")
        child_main(fast="--fast" in sys.argv)
    else:
        main(fast="--fast" in sys.argv)
