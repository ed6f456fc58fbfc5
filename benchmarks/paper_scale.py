"""Paper-scale forward/inverse SOFT benchmark (the paper's Tables 2-3
shape): per bandwidth, run FSOFT + iFSOFT under the reference, monolithic
fused, and l-chunked streaming (fp32 + bf16) schedules, and emit
speedup/efficiency rows as BENCH_paper_scale.json at the repo root --
the seed of the cross-PR perf history.

    PYTHONPATH=src python benchmarks/paper_scale.py --max-B 64

Structural guarantees (exit 1 on violation, so CI can smoke this):

  * forward AND inverse rows exist for every bandwidth run;
  * the streaming fp32 schedule is BITWISE equal to the monolithic fused
    kernel (same recurrence ops, same chunk-accumulation order);
  * the streaming bf16 schedule's relative error stays under the
    per-bandwidth gate in kernels.autotune.PRECISION_ERROR_BOUNDS;
  * the streaming schedule's VMEM-live coefficient tile
    (``est_live_coeff_bytes``) is strictly smaller than the monolithic
    schedule's at the same bandwidth.

At B >= 128 the planner goes d-free (streaming plan construction: the
dense (K, L, J) Wigner table is never materialized), the dense-table
rungs (reference, monolithic fused) are dropped, the error baseline
becomes the streaming fp32 schedule, and every rung gains a ``build``
row -- plan-construction wall time + host peak RSS measured in a fresh
subprocess (tests/progs/build_smoke.py, which enforces the >= 10x
under-the-dense-cliff canary and an absolute RSS ceiling).  B >= 256
rungs are build-only (interpret-mode transform timings are meaningless
there on CPU); B = 512 additionally sits behind the physical-RAM gate.
Bandwidths whose estimated host residency exceeds half of physical
memory are skipped LOUDLY, never silently: every skip prints its
reason.

Interpret-mode CPU timings are indicative (the streaming grid runs nL
serialized Pallas grid steps that a TPU would pipeline); the speedup
column is the cross-PR tracked quantity, the bitwise/error columns are
exact everywhere.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

if __package__ in (None, ""):                   # standalone execution
    _ROOT = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_ROOT))
    sys.path.insert(0, str(_ROOT / "src"))

import numpy as np

LADDER = (16, 32, 64, 128, 256, 512)
LCHUNK_FRACTION = 4          # streaming rows run lchunk = B / 4


def _phys_mem_bytes() -> int | None:
    try:
        return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return None


def _est_host_bytes(B: int, itemsize: int = 4, streaming: bool = False) -> int:
    """Host-side residency estimate BEFORE building anything.  Dense
    rungs are dominated by the SoftPlan's (K, L, J) Wigner table;
    streaming rungs (B >= 128, where the planner goes d-free) only pay
    the O(P*J) recurrence panels plus the chunk-boundary window stack."""
    from repro.kernels import autotune
    grid = 2 * (2 * B) ** 3 * itemsize
    est = autotune.estimate_host_plan_bytes(B, itemsize=itemsize,
                                            streaming=streaming)
    if streaming:                   # windows are host RAM on a CPU backend
        est += LCHUNK_FRACTION * 2 * (B * (B + 1) // 2) * 2 * B * itemsize
    return est + grid


def _build_rung(B: int, lchunk: int, max_rss_bytes: int):
    """Plan-construction rung measured in a FRESH subprocess
    (tests/progs/build_smoke.py) on the CPU backend: host wall time +
    host peak RSS of a streaming B-plan build, with the dense-table
    canary and the RSS ceiling enforced inside the program.  These are
    host numbers, never device ones.  Returns (row, failure)."""
    import json
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    # the child measures host RSS and build time only, on the CPU: this
    # process may already hold the chip, which one process owns at a time
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(root / "tests" / "progs" / "build_smoke.py"),
         "--bandwidth", str(B), "--lchunk", str(lchunk),
         "--max-rss-bytes", str(max_rss_bytes)],
        capture_output=True, text=True, timeout=3600, env=env)
    if proc.returncode != 0:
        return None, (f"B={B}: build_smoke.py exited {proc.returncode}: "
                      f"{(proc.stderr or proc.stdout)[-500:]}")
    j = json.loads(proc.stdout.strip().splitlines()[-1])
    return {
        "B": B, "impl": "fused_stream", "direction": "build",
        "V": 1, "lchunk": j["lchunk"], "precision": "fp32",
        "wall_s": j["plan_build_s"], "speedup_vs_reference": None,
        "efficiency": None, "max_abs_err_vs_fused": None,
        "est_live_coeff_bytes": None, "est_peak_hbm_bytes": None,
        "plan_build_s": j["plan_build_s"],
        "host_peak_rss_bytes": j["host_peak_rss_bytes"],
        "build_rss_delta_bytes": j["build_rss_delta_bytes"],
        "est_host_plan_bytes": j["est_host_plan_bytes"],
    }, None


def _time(fn, *args, reps=1):
    import jax
    jax.block_until_ready(fn(*args))            # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


def run(max_B=64, fast=False, reps=None):
    """Returns (rows, failures)."""
    import jax.numpy as jnp
    from repro import plan as plan_mod
    from repro.kernels import autotune

    import resource

    ladder = [B for B in ((16, 32) if fast else LADDER) if B <= max_B]
    mem = _phys_mem_bytes()
    rows, failures = [], []
    rng = np.random.default_rng(0)
    for B in ladder:
        streaming_rung = B >= 128   # above the planner's dense-table limit
        lchunk = max(1, B // LCHUNK_FRACTION)
        est = _est_host_bytes(B, streaming=streaming_rung)
        if mem is not None and est > mem // 2:
            print(f"SKIP B={B}: est. host residency "
                  f"{est / 2**30:.1f} GiB > half of "
                  f"{mem / 2**30:.1f} GiB physical memory")
            continue
        if B >= 256:
            # plan-construction-only rung: interpret-mode transform
            # timings are meaningless at this scale on CPU, but the
            # d-free build (the tentpole quantity) is real and tracked
            ceiling = 6 * 2**30 if B == 256 else 24 * 2**30
            row, fail = _build_rung(B, lchunk, ceiling)
            if fail:
                failures.append(fail)
            else:
                rows.append(row)
                print(f"[B={B}: build-only rung, {row['plan_build_s']:.1f}s "
                      f"build, peak RSS "
                      f"{row['host_peak_rss_bytes'] / 2**30:.2f} GiB]")
            continue
        n_reps = reps if reps is not None else (1 if B >= 64 else 2)
        # precision is pinned explicitly on every row: the bitwise check
        # below REQUIRES fused and fused_stream to run the same fp32
        # math (only the bf16 row may round), independent of whatever
        # the planner's precision heuristic would pick at this B.
        #
        # At B >= 128 the dense-table rungs (reference; monolithic fused)
        # are dropped: the planner streams, the error baseline becomes
        # the streaming fp32 schedule, and speedup_vs_reference is None.
        if streaming_rung:
            schedules = [
                ("fused_stream", dict(impl="fused", V=2, lchunk=lchunk,
                                      precision="fp32", streaming=True)),
                ("fused_stream_bf16", dict(impl="fused", V=2, lchunk=lchunk,
                                           precision="bf16",
                                           streaming=True)),
            ]
            err_base = "fused_stream"
        else:
            schedules = [
                ("reference", dict(impl="reference", V=2, precision="fp32")),
                ("fused", dict(impl="fused", V=2, precision="fp32")),
                ("fused_stream", dict(impl="fused", V=2, lchunk=lchunk,
                                      precision="fp32")),
                ("fused_stream_bf16", dict(impl="fused", V=2, lchunk=lchunk,
                                           precision="bf16")),
            ]
            err_base = "fused"
        f = (rng.normal(size=(2 * B,) * 3)
             + 1j * rng.normal(size=(2 * B,) * 3)).astype(np.complex64)
        f2 = np.stack([f, f[::-1]])
        outs, ref_t = {}, {}
        for name, kw in schedules:
            t0 = time.perf_counter()
            t = plan_mod.plan(B, dtype=jnp.float32, **kw)
            t.dwt_fn, t.idwt_fn        # charge lazy kernel/window builds
            build_s = time.perf_counter() - t0
            peak_rss = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024
            d = t.describe()
            fwd_t = _time(t.forward, f, reps=n_reps)
            fhat = np.asarray(t.forward(f))
            inv_t = _time(t.inverse, fhat, reps=n_reps)
            outs[name] = (fhat, np.asarray(t.inverse(fhat)))
            if streaming_rung:
                eff_f = eff_i = None   # V-lane amortization costs another
            else:                      # 2x B>=128 interpret pass; skip it
                # lane amortization: V transforms on one packed launch vs
                # V single launches (> 1 = packing pays)
                eff_f = 2 * fwd_t / _time(t.forward_batch, f2, reps=n_reps)
                fhat2 = np.stack([fhat, outs[name][0]])
                eff_i = 2 * inv_t / _time(t.inverse_batch, fhat2,
                                          reps=n_reps)
            if name == "reference":
                ref_t = {"forward": fwd_t, "inverse": inv_t}
            for direction, wall, eff in (("forward", fwd_t, eff_f),
                                         ("inverse", inv_t, eff_i)):
                err = None
                if name != err_base and err_base in outs:
                    base = outs[err_base][0 if direction == "forward"
                                          else 1]
                    mine = outs[name][0 if direction == "forward" else 1]
                    err = float(np.abs(mine - base).max())
                rows.append({
                    "B": B, "impl": name, "direction": direction,
                    "V": d["V"], "lchunk": d["lchunk"],
                    "precision": d["precision"],
                    "wall_s": wall,
                    "speedup_vs_reference":
                        (ref_t[direction] / wall) if ref_t else None,
                    "efficiency": eff,
                    "max_abs_err_vs_fused": err,
                    "est_live_coeff_bytes": d["est_live_coeff_bytes"],
                    "est_peak_hbm_bytes": d["est_peak_hbm_bytes"],
                    "plan_build_s": build_s,
                    "host_peak_rss_bytes": peak_rss,
                })
        # ---- structural checks ------------------------------------------
        dirs = {(r["impl"], r["direction"]) for r in rows if r["B"] == B}
        for name, _ in schedules:
            for direction in ("forward", "inverse"):
                if (name, direction) not in dirs:
                    failures.append(f"B={B}: missing {name}/{direction} row")
        if not streaming_rung:
            for i, (a, b) in enumerate(zip(outs["fused_stream"],
                                           outs["fused"])):
                if not np.array_equal(a, b):
                    failures.append(
                        f"B={B}: streaming fp32 "
                        f"{('forward', 'inverse')[i]} is not bitwise-equal "
                        f"to the monolithic fused kernel")
        bound = autotune.PRECISION_ERROR_BOUNDS[B]
        for i, (a, b) in enumerate(zip(outs["fused_stream_bf16"],
                                       outs[err_base])):
            rel = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
            if rel > bound:
                failures.append(
                    f"B={B}: bf16 {('forward', 'inverse')[i]} rel err "
                    f"{rel:.2e} over the {bound:.2e} error-table gate")
        if streaming_rung:
            # the tentpole invariant: paper-scale plans are d-free, and a
            # fresh-subprocess build stays >= 10x under the dense cliff
            # (enforced inside build_smoke.py)
            if not d["streaming"]:
                failures.append(f"B={B}: planner materialized the dense "
                                f"table on a paper-scale rung")
            row, fail = _build_rung(B, lchunk, 2 * 2**30)
            if fail:
                failures.append(fail)
            else:
                rows.append(row)
            live = {r["impl"]: r["est_live_coeff_bytes"]
                    for r in rows if r["B"] == B and r["direction"] != "build"}
        else:
            live = {r["impl"]: r["est_live_coeff_bytes"]
                    for r in rows if r["B"] == B}
            if not live["fused_stream"] < live["fused"]:
                failures.append(
                    f"B={B}: streaming live coeff bytes "
                    f"{live['fused_stream']} not below monolithic "
                    f"{live['fused']}")
        print(f"[B={B}: {len([r for r in rows if r['B'] == B])} rows, "
              f"lchunk={lchunk}, live coeff {live['fused_stream']}B]")
    return rows, failures


def main(fast=False, max_B=64, out=None, check_against=None, reps=None,
         append=False):
    """append=True extends the committed BENCH_paper_scale.json history
    (the cross-PR perf feed CI maintains) instead of overwriting it; the
    schema-loss guard runs against the pre-append baseline either way."""
    from benchmarks import emit

    rows, failures = run(max_B=max_B, fast=fast, reps=reps)
    print("# paper_scale (forward+inverse speedup/efficiency)")
    print("B,impl,direction,wall_s,speedup_vs_reference,efficiency,"
          "lchunk,precision,live_coeff_B,plan_build_s,host_peak_rss_B")

    def _fmt(v, spec=".2f"):
        return "-" if v is None else format(v, spec)

    for r in rows:
        print(f"{r['B']},{r['impl']},{r['direction']},{r['wall_s']:.4f},"
              f"{_fmt(r['speedup_vs_reference'])},{_fmt(r['efficiency'])},"
              f"{r['lchunk']},{r['precision']},{r['est_live_coeff_bytes']},"
              f"{_fmt(r.get('plan_build_s'))},"
              f"{_fmt(r.get('host_peak_rss_bytes'), 'd')}")
    if check_against:
        # guard BEFORE writing: an append must never launder a schema loss
        # into the baseline it is then checked against
        failures += emit.check_schema(rows, check_against)
    if append:
        path = emit.append_root_json("paper_scale", rows, out=out)
        verb = "appended to"
    else:
        path = emit.emit_root_json("paper_scale", rows, out=out)
        verb = "wrote"
    print(f"{verb} {path} ({len(rows)} rows, sha {emit.git_sha()})")
    if failures:
        for f in failures:
            print("FAIL:", f)
        raise SystemExit(1)
    print("structural checks: OK")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-B", type=int, default=64)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="output path (default BENCH_paper_scale.json at "
                         "the repo root)")
    ap.add_argument("--check-against", default=None,
                    help="committed baseline JSON for the schema-loss guard")
    ap.add_argument("--append", action="store_true",
                    help="append rows to the existing artifact (perf "
                         "history) instead of overwriting it")
    args = ap.parse_args()
    main(fast=args.fast, max_B=args.max_B, out=args.out,
         check_against=args.check_against, reps=args.reps,
         append=args.append)
