"""The bank cell at a small size on the CPU: whole runs through the harness,
the check failing on each planted fault and on the lower-precision
control, the plain bank reference, and the cell's per-layer readers on
synthetic runs."""
import dataclasses
import json
import pathlib

import numpy as np
import pytest

from bench import kernels, opnames, reference_bank, workcount
from bench import trace as tr
from bench.harness import Run
from bench.loader import Catalog

HERE = pathlib.Path(__file__).resolve().parent
SMALL = {"B": 8, "M": 20}          # 3 chunks of V = 8, 4 lanes padded
CELL = "bank_b64.query"
BANK_METRICS = ("dwt_roofline.bank", "peak_ms.bank",
                "readback_mb.bank", "dispatch_ms.bank",
                "device_idle_pct.bank")


def small_bank_root(tmp_path):
    from bench.test_bench_run import small_root

    root = small_root(tmp_path)
    path = root / HERE.name / "configs" / "bank_b64.json"
    cfg = json.loads(path.read_text())
    cfg.update(SMALL)
    path.write_text(json.dumps(cfg))
    return root


def _config(**plan):
    cfg = json.loads((HERE / "configs" / "bank_b64.json").read_text())
    cfg.update(SMALL, plan=plan)
    return cfg


def _driver(**plan):
    from bench.drivers import bank

    cat = Catalog(HERE.parent)
    drv = bank.Driver(_config(**plan), cat.traffic("query"), seed=2**33 + 5)
    drv.setup()
    drv.window(0.5)
    return drv


def _limits():
    return _config()["limits"]


def _fails(readings):
    return [n for n, lim in _limits().items() if not readings[n] <= lim]


# -- whole runs ----------------------------------------------------------------

def test_sound_run_is_correct_and_reports_its_metrics(tmp_path):
    from bench.test_bench_run import run

    root = small_bank_root(tmp_path)
    r = run(root, CELL)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"setup_s", "match_p50_ms"}
    assert set(r["checks"]) == set(_limits())
    assert r["checks"]["winner_misses"]["value"] == 0


def test_traced_run_reads_the_programs_counters_and_spans(tmp_path):
    from bench.test_bench_run import run

    r = run(small_bank_root(tmp_path), CELL, trace=1)
    assert r["correct"] is True, r["checks"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    # 3 chunks of 8 lanes: an int32 index and 7 f32 values a lane, and the
    # query's norm
    assert got["readback_mb.bank"] == pytest.approx((24 * 32 + 4) / 1e6)
    assert got["dispatch_ms.bank"] > 0
    assert set(got) <= set(BANK_METRICS)


# -- the check against planted faults and the control --------------------------

@pytest.fixture(scope="module")
def sound():
    return _driver()


def test_check_passes_on_the_program(sound):
    got = sound.readings()
    assert _fails(got) == [], got
    assert len(sound.sample()) >= len(sound.answers) + 12


def _planted(drv, fault):
    out = []
    B, n = drv.B, 2 * drv.B
    for k, best, results in drv.answers:
        if fault == "wrong_winner":
            best = (best + 1) % drv.M
        else:
            res = []
            for r in results:
                if fault == "moved_peak":       # another cell of the grid
                    i, j, kk = r.index
                    r = dataclasses.replace(r, index=((i + B) % n, j, kk))
                elif fault == "altered_peak":
                    r = dataclasses.replace(r, peak=r.peak * 1.01)
                elif fault == "corrupted_neighbour":
                    s = list(r.stencil)
                    s[3] += 0.01 * abs(r.peak)
                    r = dataclasses.replace(r, stencil=tuple(s))
                res.append(r)
            results = res
        out.append((k, best, results))
    return out


@pytest.mark.parametrize("fault,caught_by", [
    ("wrong_winner", "winner_misses"), ("moved_peak", "argmax_gap"),
    ("altered_peak", "peak_max"), ("corrupted_neighbour", "stencil_max")])
def test_check_fails_on_each_planted_fault(sound, fault, caught_by):
    answers = sound.answers
    sound.answers = _planted(sound, fault)
    try:
        failed = _fails(sound.readings())
    finally:
        sound.answers = answers
    assert caught_by in failed, failed


def test_program_bf16_path_is_the_control():
    got = _driver(precision="bf16").readings()
    assert _fails(got), got


# -- the plain reference -------------------------------------------------------

def test_reference_recovers_a_planted_rotation_and_refines_as_the_program():
    from bench.drivers import bank
    from repro.so3.correlate import refine_stencils

    B = 8
    templates = bank.template_bank(B, 3, seed=7)
    f, m, rot = bank.query_pool(templates, 1, seed=7)[0]
    ref = reference_bank.match(f, templates[m])
    steps = [bank.angle_error(x, y) * B / np.pi
             for x, y in zip(ref["euler"], rot)]
    assert max(steps) < 1.5
    re = ref["re"]
    stencil = [ref["peak"]] + [re[p] for p in reference_bank.neighbours(
        ref["index"], 2 * B)]
    prog = refine_stencils(B, [ref["index"]], [stencil])[0]
    np.testing.assert_allclose(prog.euler, ref["euler"], atol=1e-12)


def test_reference_neighbours_wrap_alpha_gamma_and_stop_at_beta_edges():
    assert reference_bank.neighbours((0, 0, 15), 16) == [
        (15, 0, 15), (1, 0, 15), (0, 0, 15), (0, 1, 15), (0, 0, 14),
        (0, 0, 0)]


# -- the readers ----------------------------------------------------------------

TPU_OPS = [
    ('%idwt_fused.1 = f32[2080,128,128]{2,1,0:T(8,128)} custom-call('
     '%copy.1), custom_call_target="tpu_custom_call"', 0, 100),
    ('%grid_peaks.1 = (f32[8,1,128]{2,1,0:T(1,128)S(1)}, s32[8,1,128]'
     '{2,1,0:T(1,128)S(1)}) custom-call(%bitcast.1), custom_call_target='
     '"tpu_custom_call"', 100, 40),
    ('%fusion.7 = f32[8,16384,128]{2,1,0:T(8,128)} fusion(%grid_peaks.1), '
     'kind=kLoop', 140, 10),
    ('%idwt_fused.1 = f32[2080,128,128]{2,1,0:T(8,128)} custom-call('
     '%copy.1), custom_call_target="tpu_custom_call"', 200, 100),
]


def _run(ops=TPU_OPS, obs=None, completed=2, templates=16):
    t = None if ops is None else tr.Reduced(
        window_s=1e-6, busy_s=250e-9, ops=ops, top_ops=[], idle_gaps=[])
    return Run(workload=CELL, config={"B": 64}, device_kind="TPU v5 lite",
               setup_s=1.0, window_s=1e-6, trace=t, obs=obs or {},
               counters={"completed": completed, "templates": templates,
                         "launches": 2})


SPANS = {"correlate.dispatch": {"count": 2, "total": 0.006},
         "correlate.readback_bytes": {"count": 2, "total": 2 * 292.0}}


@pytest.mark.parametrize("metric,expected", [
    ("dwt_roofline.bank",
     100 * workcount.least_seconds(64, 16, "inverse", "TPU v5 lite")[0]
     / 200e-9),
    ("peak_ms.bank", 40e-6 / 2),           # 40 ns of grid_peaks, 2 queries
    ("readback_mb.bank", 292.0 / 1e6),
    ("dispatch_ms.bank", 3.0),
    ("device_idle_pct.bank", 75.0),
])
def test_bank_reader_on_a_synthetic_run(metric, expected):
    assert Catalog(HERE.parent).reader(metric)(_run(obs=SPANS)) == \
        pytest.approx(expected)


@pytest.mark.parametrize("metric", BANK_METRICS)
def test_bank_reader_finds_nothing_where_there_is_nothing(metric):
    """No trace, no completed query, and a program (the parent's) without
    the bank path's counters or kernels: nothing to read, and no error."""
    read = Catalog(HERE.parent).reader(metric)
    assert read(_run(ops=None, obs=SPANS, completed=0, templates=0)) is None
    if metric in ("dwt_roofline.bank", "peak_ms.bank"):
        assert read(_run(ops=[TPU_OPS[2]])) is None
    elif metric != "device_idle_pct.bank":
        assert read(_run(obs={"service.launch": {"count": 1,
                                                 "total": 1.0}})) is None


def test_dwt_roofline_of_the_bank_ignores_the_peak_kernel():
    """bench.kernels.is_dwt counts any Pallas kernel, grid_peaks among
    them; the bank's reader picks the iDWT by its name alone."""
    assert kernels.is_dwt(TPU_OPS[1][0])
    assert [op[1] for op in opnames.named(TPU_OPS, kernels.DWT_NAMES)] \
        == [0, 200]
    assert [op[1] for op in opnames.named(TPU_OPS, ("grid_peaks",))] == \
        [100]
