"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, mix or metric sits in a
file of its own under the benchmark's directory, named as in
``BENCHMARK.json``:

  configurations  the ``file`` of the ``configs`` entry (JSON)
  traffic mixes   traffic/<mix>.json
  metric readers  metrics/<metric>.py, with ``read(run) -> float | None``;
                  a metric ``<name>.<cell kind>`` whose reading is the same
                  in every cell, such as ``device_idle_pct.served``, may
                  share metrics/<name>.py
  drivers         drivers/<driver>.py, named by a configuration's
                  ``driver`` key

so a later cell, mix or metric is added as new files and entries, with no
edit to a file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class Catalog:
    """BENCHMARK.json at ``root`` and the files it names."""

    def __init__(self, root: pathlib.Path, bench_dir: pathlib.Path | None = None):
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir) if bench_dir else \
            self.root / BENCH_DIR.name
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {[w['name'] for w in self.spec['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench_dir / "traffic" / f"{name}.json")
                          .read_text())

    def metrics(self, workload: str, per_layer: bool) -> list[dict]:
        """The metrics a run of ``workload`` reports: its end-to-end ones
        with ``--trace 0``, its per-layer ones with ``--trace 1``."""
        entries = self.spec["per_layer" if per_layer else "end_to_end"]
        return [m for m in entries
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str):
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file() and "." in metric:
            path = path.with_name(metric.rsplit(".", 1)[0] + ".py")
        return _load(path, f"bench_metric_{metric}").read

    def driver(self, name: str):
        return _load(self.bench_dir / "drivers" / f"{name}.py",
                     f"bench_driver_{name}").Driver


def _load(path: pathlib.Path, module_name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        module_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
