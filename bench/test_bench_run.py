"""Whole runs of each cell on the CPU at a small size, with the look for a
chip skipped: the harness, the loader finding files by name, the check, and
the check failing on the lower-precision control and on a broken timed
path.

Each run is a child process (``python test_bench_run.py ...``), so JAX's
global settings and the program's caches of one run touch no other test.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
SMALL_B = {"ladder_b128": 16, "match_b64": 8}
SECONDS = 1.0
TIMEOUT_S = 300


def small_root(tmp_path: pathlib.Path, plan=None) -> pathlib.Path:
    """A checkout-like tree: BENCHMARK.json and a copy of the benchmark's
    directory with each configuration at a small bandwidth."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / HERE.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, B in SMALL_B.items():
        path = root / HERE.name / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["B"] = B
        if plan is not None and "plan" in cfg:
            cfg["plan"] = plan
        path.write_text(json.dumps(cfg))
    return root


def run(root, workload, *, fault="none", trace=0, seed=12345):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    p = subprocess.run(
        [sys.executable, str(root / HERE.name / pathlib.Path(__file__).name),
         str(root), workload, str(seed), str(trace), fault,
         str(REPO / "src")],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# -- faults planted under the timed path ------------------------------------

def _alter_inverse():
    from repro.plan import transform

    inverse = transform.Transform.inverse

    def broken(self, fhat, **kw):
        g = inverse(self, fhat, **kw)
        return g.at[:, 0, :].multiply(1.1)     # one beta row 10% off
    transform.Transform.inverse = broken


def _alter_forward():
    from repro.plan import transform

    forward = transform.Transform.forward

    def broken(self, f, **kw):
        c = forward(self, f, **kw)
        return c.at[-1].multiply(1.1)          # the top degree 10% off
    transform.Transform.forward = broken


def _drop_half_lanes():
    from repro.so3 import correlate

    grids = correlate.CorrelationEngine.correlation_grids

    def broken(self, fs, gs):
        C = grids(self, fs, gs)
        C[len(fs) // 2:] = 0        # the second half of the batch left out
        return C
    correlate.CorrelationEngine.correlation_grids = broken


def _alter_answer():
    import dataclasses

    from repro.so3 import service

    peak = service.peak_euler

    def broken(C, B, **kw):
        r = peak(C, B, **kw)
        i, j, k = r.index
        return dataclasses.replace(r, index=(i, j, (k + 1) % (2 * B)),
                                   gamma=r.gamma + 3.14159 / B)
    service.peak_euler = broken


def _bf16_reference_in_place():
    from bench.drivers import served
    from repro.so3 import correlate

    correlate.CorrelationEngine.correlation_grids = served.control_grids


FAULTS = {"none": lambda: None, "alter_inverse": _alter_inverse,
          "alter_forward": _alter_forward, "drop_half": _drop_half_lanes,
          "alter_answer": _alter_answer,
          "bf16_control": _bf16_reference_in_place}


# -- the tests ----------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", ["ladder_b128.single",
                                      "match_b64.served"])
def test_sound_run_is_correct_and_reports_its_metrics(root, workload):
    r = run(root, workload)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    names = {m["name"] for m in json.loads(
        (root / "BENCHMARK.json").read_text())["end_to_end"]
        if workload in m.get("workloads", [workload])}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    ("ladder_b128.single", "alter_inverse"),
    ("ladder_b128.single", "alter_forward"),
    ("match_b64.served", "drop_half"),
    ("match_b64.served", "alter_answer"),
    ("match_b64.served", "bf16_control"),
])
def test_broken_timed_path_is_not_correct(root, workload, fault):
    assert run(root, workload, fault=fault)["correct"] is False


def test_program_bf16_path_is_the_ladder_control(tmp_path):
    root = small_root(tmp_path, plan={"precision": "bf16"})
    assert run(root, "ladder_b128.single")["correct"] is False


def test_added_config_mix_and_metric_are_found_by_name(root, tmp_path):
    """A later cell adds files and entries only; the harness finds them."""
    new = tmp_path / "added"
    shutil.copytree(root, new)
    bench = new / HERE.name
    cfg = json.loads((bench / "configs" / "ladder_b128.json").read_text())
    cfg["name"] = "ladder_b8"
    cfg["B"] = 8
    (bench / "configs" / "ladder_b8.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "triple.json").write_text(
        json.dumps({"loop": "closed", "pool": 3}))
    (bench / "metrics" / "steps_done.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ladder_b8", "source": "x",
                            "file": "bench/configs/ladder_b8.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "ladder_b8.triple",
                              "config": "ladder_b8", "traffic": "triple",
                              "chips": 1, "why": "x"})
    spec["end_to_end"].append({"name": "steps_done", "unit": "steps",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["ladder_b8.triple"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    r = run(new, "ladder_b8.triple")
    assert r["correct"] is True
    # roundtrip_ms names only the cells it was measured in
    assert set(r["metrics"]) == {"setup_s", "steps_done"}
    assert r["metrics"]["steps_done"]["value"] == r["attempted"]


def test_traced_run_reads_the_trace(root):
    r = run(root, "ladder_b128.single", trace=1)
    assert r["correct"] is True
    assert r["device"]["window_s"] > 0
    assert "device_ops" in r["breakdown"] and "idle_gaps" in r["breakdown"]


def _child(argv):
    root, workload, seed, trace, fault, src = argv
    sys.path[:0] = [root, src]
    from bench import harness

    FAULTS[fault]()
    t0 = harness.time.perf_counter()
    result = harness.run_cell(pathlib.Path(root), workload, int(seed),
                              SECONDS, trace == "1", t0, require_chip=False)
    harness.print_result(result)


if __name__ == "__main__":
    _child(sys.argv[1:])
