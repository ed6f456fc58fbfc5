import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Patch dry-run artifacts with the analytic HBM-traffic estimate
(launch.flops.analytic_bytes) -- trace-only, no recompilation.

    PYTHONPATH=src python -m repro.launch.patch_bytes [--out artifacts/dryrun]
"""
import argparse
import glob
import json

from repro.launch import dryrun as dr
from repro.launch.flops import analytic_bytes
from repro.launch.mesh import make_ctx, make_production_mesh
from repro import configs
from repro.configs.base import shapes_for


def build(cell):
    multi = cell["mesh"] == "pod2x16x16"
    mesh = make_production_mesh(multi_pod=multi)
    ctx = make_ctx(mesh)
    arch = cell["arch"]
    if arch.startswith("soft_b"):
        fn, args = dr.build_soft(configs.SOFT_CONFIGS[arch], ctx, mesh,
                                 "forward" if cell["shape"] == "forward"
                                 else "inverse")
        return fn, args, mesh
    cfg = configs.get(arch)
    shape = {s.name: s for s in shapes_for(cfg)}[cell["shape"]]
    if shape.kind == "train":
        fn, args = dr.build_train(cfg, ctx, shape, cell.get("opt") or "adamw")
    elif shape.kind == "prefill":
        fn, args = dr.build_prefill(cfg, ctx, shape)
    else:
        fn, args = dr.build_decode(cfg, ctx, shape)
    return fn, args, mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    for path in sorted(glob.glob(os.path.join(args.out, "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        if "bytes_analytic_per_device" in cell:
            continue
        fn, fargs, mesh = build(cell)
        b = analytic_bytes(fn, *fargs, mesh_size=mesh.size)
        cell["bytes_analytic_per_device"] = b / mesh.size
        with open(path, "w") as f:
            json.dump(cell, f, indent=1)
        print(f"{os.path.basename(path)}: "
              f"analytic {b / mesh.size:.3e} B/dev "
              f"(was corrected {cell.get('bytes_corrected_per_device', -1):.3e})")


if __name__ == "__main__":
    main()
