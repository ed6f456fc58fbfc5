"""The readers of the served cell's launch split: the program's
``correlate.*`` spans and ``correlate.readback_bytes`` histogram per launch
or per request, nothing where the program recorded none, and the four
metrics in a traced served run on the CPU at a small size."""
import pathlib

import pytest

from bench.harness import Run
from bench.loader import Catalog

HERE = pathlib.Path(__file__).resolve().parent
LAUNCH_METRICS = ("dispatch_ms.served", "device_wait_ms.served",
                  "readback_ms.served", "readback_mb.served")


def _run(obs):
    return Run(workload="match_b64.served", config={"B": 64},
               device_kind="k", setup_s=1.0, window_s=10.0, obs=obs,
               counters={"completed": 5, "launches": 4, "transforms": 5})


SPANS = {name: {"count": 4, "total": total} for name, total in [
    ("correlate.pair", 0.004), ("correlate.dispatch", 0.4),
    ("correlate.wait", 0.08), ("correlate.readback", 0.06),
    ("correlate.readback_bytes", 5 * 128 ** 3 * 8.0)]}


@pytest.mark.parametrize("metric,expected", [
    ("dispatch_ms.served", 101.0),          # (4 + 400) ms over 4 launches
    ("device_wait_ms.served", 20.0),
    ("readback_ms.served", 15.0),
    ("readback_mb.served", 128 ** 3 * 8 / 1e6),   # 16.8 MB per request
])
def test_launch_reader_divides_the_window(metric, expected):
    assert Catalog(HERE.parent).reader(metric)(_run(SPANS)) == \
        pytest.approx(expected)


@pytest.mark.parametrize("metric", LAUNCH_METRICS)
def test_launch_reader_finds_nothing_without_the_spans(metric):
    """The parent program records no correlate.* spans: nothing to read,
    and no error.  Nor is there a reading with no launch or answer."""
    read = Catalog(HERE.parent).reader(metric)
    assert read(_run({"service.launch": {"count": 4, "total": 0.5}})) is None
    idle = _run(SPANS)
    idle.counters = {"completed": 0, "launches": 0, "transforms": 0}
    assert read(idle) is None


def test_traced_served_run_reports_the_launch_split(tmp_path):
    from bench.test_bench_run import SMALL_B, run, small_root

    root = small_root(tmp_path)
    r = run(root, "match_b64.served", trace=1)
    assert r["correct"] is True
    got = {name: r["metrics"][name]["value"] for name in LAUNCH_METRICS}
    assert all(v > 0 for v in got.values()), got
    # every request reads back its own (2B)^3 complex64 grid, no more
    B = SMALL_B["match_b64"]
    assert got["readback_mb.served"] == pytest.approx((2 * B) ** 3 * 8 / 1e6)
