"""The reduction from trace to metrics, on small recorded event lists."""
import json
import pathlib

import pytest

from bench import trace as tr
from bench import workcount

HERE = pathlib.Path(__file__).resolve().parent


def _ops(*spans):
    return [(f"op{i}", s, d) for i, (s, d) in enumerate(spans)]


def test_merge_and_busy_union():
    ops = _ops((0, 10), (5, 10), (20, 5), (24, 1), (40, 0))
    assert tr.merge(ops) == [(0, 15), (20, 25), (40, 40)]


def test_clip_cuts_events_to_the_window():
    ops = _ops((0, 10), (15, 10), (30, 10))
    assert tr.clip(ops, 5, 35) == [("op0", 5, 5), ("op1", 15, 10),
                                   ("op2", 30, 5)]


def test_reduce_events_busy_idle_and_labels():
    host = [("bench.window", 100, 1000), ("outer", 100, 1000),
            ("inner", 400, 200)]
    chips = {"/device:TPU:0": [("k", 50, 100), ("k", 300, 100),
                               ("fft", 700, 100), ("late", 1200, 50)]}
    r = tr.reduce_events(chips, host, "bench.window")
    assert r.window_s == pytest.approx(1000e-9)
    # inside [100, 1100): k 100..150, k 300..400, fft 700..800
    assert r.busy_s == pytest.approx(250e-9)
    assert r.top_ops[0] == ["k", pytest.approx(150e-9)]
    # gaps 150..300 (mid 225), 400..700 (mid 550), 800..1100 (mid 950)
    assert [[n, round(s * 1e9)] for n, s in r.idle_gaps] == \
        [["inner", 300], ["outer", 300], ["outer", 150]]
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(750e-9)


def test_recorder_spans_land_on_the_trace_clock():
    ev = [{"name": "service.refine", "ph": "X", "ts": 2.0, "dur": 3.0}]
    assert tr.recorder_spans(ev, 1000) == [("service.refine", 3000.0,
                                            3000.0)]


def test_op_seconds_matches_by_name():
    ops = [("a.1", 0, 10), ("b", 0, 5), ("a.2", 0, 20)]
    assert tr.op_seconds(ops, lambda n: n.startswith("a")) == \
        (pytest.approx(30e-9), 2)


# operation names as a TPU trace gives them (HLO instructions of the
# ladder's programs compiled for a v5e)
TPU_OPS = [
    ('%idwt_fused.1 = f32[8256,256,16]{2,1,0:T(8,128)} custom-call('
     '%copy-done.12, %copy-done.9, /*index=5*/%copy.69), custom_call_target='
     '"tpu_custom_call", operand_layout_constraints={s32[17]{0}}', 0, 100),
    ('%fusion.3 = f32[8256,256,16]{2,1,0:T(8,128)} fusion(%idwt_fused.1, '
     '%pad_clamp_fusion), kind=kCustom, calls=%fused_computation.3', 100, 30),
    ('%convolution_add_fusion.1 = f32[256,256,256]{2,1,0:T(8,128)} fusion('
     '%fusion.8, %bitcast.59), kind=kOutput, calls=%fused_computation.15',
     130, 20),
    ('%dwt_fused.2 = f32[136,16,16]{2,1,0:T(8,128)S(1)} custom-call('
     '%copy-done.12, %copy.69), custom_call_target="tpu_custom_call"', 200, 50),
    ('%custom-call.2 = c64[16,31,31]{2,0,1:T(8,128)} custom-call(%copy.80, '
     '%copy.81), custom_call_target="X64Combine"', 260, 10),
    ("dwt_streaming.4", 300, 5),
]


def test_kernels_are_found_by_their_instruction_not_their_operands():
    from bench import kernels

    assert [kernels.is_dwt(n) for n, _, _ in TPU_OPS] == \
        [True, False, False, True, False, True]
    assert kernels.dwt_seconds(TPU_OPS) == (pytest.approx(155e-9), 3)
    # busy 0..150, 200..250, 260..270, 300..305: 215 ns, of it 155 DWT
    assert kernels.grid_seconds(TPU_OPS) == (pytest.approx(60e-9), 3)


def test_kernel_readers_on_a_tpu_trace():
    from bench.harness import Run
    from bench.loader import Catalog

    cat = Catalog(HERE.parent)
    t = tr.Reduced(window_s=1e-6, busy_s=215e-9, ops=TPU_OPS, top_ops=[],
                   idle_gaps=[])
    run = Run(workload="ladder_b128.single", config={"B": 128},
              device_kind="TPU v5 lite", setup_s=1.0, window_s=1e-6,
              steps=2, trace=t)
    assert cat.reader("grid_ms.ladder")(run) == pytest.approx(30e-6)
    least = sum(workcount.least_seconds(128, 2, d, "TPU v5 lite")[0]
                for d in workcount.DIRECTIONS)
    assert cat.reader("dwt_roofline.ladder")(run) == \
        pytest.approx(100 * least / 155e-9)
    run.trace = tr.Reduced(window_s=1e-6, busy_s=0, ops=TPU_OPS[1:3],
                           top_ops=[], idle_gaps=[])
    assert cat.reader("dwt_roofline.ladder")(run) is None


def test_work_count_at_the_paper_size():
    ops, nbytes = workcount.work(128, 1, "inverse")
    assert ops == pytest.approx(2.863e9, rel=1e-3)
    assert nbytes == pytest.approx(155.6e6, rel=1e-3)
    t, bound = workcount.least_seconds(128, 1, "forward", "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        workcount.peaks("no such chip")


def test_metric_of_one_cell_kind_falls_back_to_the_shared_reader():
    from bench.harness import Run
    from bench.loader import Catalog

    cat = Catalog(HERE.parent)
    t = tr.Reduced(window_s=2.0, busy_s=0.5, ops=[], top_ops=[],
                   idle_gaps=[])
    run = Run(workload="w", config={}, device_kind="k", setup_s=1.0,
              window_s=2.0, trace=t)
    for name in ("device_idle_pct.ladder", "device_idle_pct.served"):
        assert cat.reader(name)(run) == pytest.approx(75.0)
    with pytest.raises(FileNotFoundError):
        cat.reader("no_such_metric.served")
