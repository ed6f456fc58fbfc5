"""repro.obs units: the bounded Recorder (spans / counters / histogram
quantiles under eviction), spans on the profiler's host plane,
Chrome-trace export + the structural validator, obs.time_fn's
measurement contract, the planner/engine/service instrumentation hooks,
and tracing's bitwise invisibility to transform outputs."""
import glob
import json
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import Recorder


# ---------------------------------------------------------------------------
# Recorder primitives
# ---------------------------------------------------------------------------

def test_recorder_spans_counters_quantiles():
    rec = Recorder()
    with rec.span("a.x", foo=1):
        pass
    rec.inc("c", 2)
    rec.observe("h", 1.0)
    rec.observe("h", 3.0)
    q = rec.quantiles("h")
    assert q["count"] == 2 and q["mean"] == 2.0 and q["max"] == 3.0
    assert {"p50", "p95", "p99", "total"} <= q.keys()
    assert rec.quantiles("never-observed") is None
    assert rec.counters() == {"c": 2}
    ev = rec.events()[0]
    assert ev["name"] == "a.x" and ev["ph"] == "X" and ev["args"] == {"foo": 1}
    assert ev["dur"] >= 0
    # spans feed the same-name histogram
    assert rec.quantiles("a.x")["count"] == 1
    rec.clear()
    assert rec.events() == [] and rec.counters() == {}


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """Under a jax.profiler capture a Recorder span is also one host event
    of the same name, on the profiler's clock.  The annotation encloses
    the Recorder's own reading, which covers the 20 ms sleep; the two
    differ by the span's bookkeeping, which host scheduling stretches by
    a few ms at most, never by the factor 1000 of a wrong unit."""
    import jax
    from jax.profiler import ProfileData

    rec = Recorder()
    with jax.profiler.trace(str(tmp_path)):
        with rec.span("obs.test.sleep", k=1):
            time.sleep(0.02)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    got = [e.duration_ns for plane in ProfileData.from_file(path).planes
           if plane.name == "/host:CPU" for line in plane.lines
           for e in line.events if e.name == "obs.test.sleep"]
    assert len(got) == 1
    dur_ns = rec.events()[0]["dur"] * 1e3
    assert 20e6 <= dur_ns <= got[0] <= dur_ns + 5e6, (got[0], dur_ns)


def test_recorder_memory_is_bounded():
    rec = Recorder(max_events=8, max_samples=4)
    for i in range(100):
        with rec.span("s"):
            pass
        rec.observe("h", float(i))
    assert len(rec.events()) == 8          # ring evicts oldest events
    q = rec.quantiles("h")
    assert q["count"] == 100               # running stats see everything
    assert q["max"] == 99.0
    assert q["p50"] >= 96.0                # quantile ring holds the tail


def test_recorder_rows_match_emit_row_shape():
    rec = Recorder()
    rec.observe("lat", 0.5)
    rec.inc("hits")
    rows = rec.rows()
    kinds = {r["kind"] for r in rows}
    assert kinds == {"histogram", "counter"}
    assert all(isinstance(r, dict) and "name" in r for r in rows)
    h = next(r for r in rows if r["kind"] == "histogram")
    assert {"count", "mean", "p50", "p95", "p99", "max"} <= h.keys()


def test_set_recorder_swaps_and_restores():
    rec = Recorder()
    old = obs.set_recorder(rec)
    try:
        obs.inc("x")
        with obs.span("y"):
            pass
        assert rec.counters() == {"x": 1}
        assert old.counters().get("x") is None
    finally:
        assert obs.set_recorder(old) is rec
    assert obs.get_recorder() is old


# ---------------------------------------------------------------------------
# Chrome-trace export + validation
# ---------------------------------------------------------------------------

def test_chrome_trace_export_roundtrip(tmp_path):
    rec = Recorder()
    with rec.span("plan.build", B=8):
        with rec.span("plan.schedule"):
            pass
    path = rec.dump_chrome_trace(tmp_path / "sub" / "t.json")
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    # nested spans export ts-sorted (parent opened first), so the
    # validator's monotonicity requirement holds by construction
    assert [e["name"] for e in doc["traceEvents"]] == \
        ["plan.build", "plan.schedule"]
    assert obs.check_chrome_trace(
        doc, required_names=("plan.build", "plan.schedule")) == []


def test_check_chrome_trace_catches_structural_damage():
    assert obs.check_chrome_trace({}) == ["trace has no traceEvents"]
    assert obs.check_chrome_trace({"traceEvents": []}) \
        == ["trace has no traceEvents"]
    bad = {"traceEvents": [
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 1.0},
        {"name": "a", "ph": "X", "ts": 1.0, "dur": -2.0},
        {"ph": "X", "ts": 2.0},
        {"name": "c", "ph": "?", "ts": 3.0},
    ]}
    fails = obs.check_chrome_trace(bad, required_names=("zz",))
    assert any("not monotonic" in f for f in fails)
    assert any("negative" in f for f in fails)
    assert sum("missing name/ph" in f for f in fails) == 2
    assert any("'zz' missing" in f for f in fails)


# ---------------------------------------------------------------------------
# time_fn
# ---------------------------------------------------------------------------

def test_time_fn_measures_and_records():
    rec = Recorder()
    calls = []

    def fn(x):
        calls.append(x)
        return x * 2

    per = obs.time_fn(fn, 3, reps=5, name="bench.fn", recorder=rec,
                      sync=lambda r: r, key="k")
    assert per >= 0.0
    assert calls == [3] * 6                # 1 untimed warmup + 5 timed
    ev = rec.events()[0]
    assert ev["name"] == "bench.fn"
    assert ev["args"]["reps"] == 5 and ev["args"]["key"] == "k"
    assert ev["args"]["per_call_s"] == pytest.approx(per)
    assert rec.quantiles("bench.fn")["count"] == 1


# ---------------------------------------------------------------------------
# layer instrumentation
# ---------------------------------------------------------------------------

def test_plan_build_emits_spans_and_cache_counters():
    from repro.plan import transform
    rec = Recorder()
    old = obs.set_recorder(rec)
    try:
        transform.clear_cache()
        t = transform.plan(8, impl="fused", V=2, tk=4)
        assert transform.plan(8, impl="fused", V=2, tk=4) is t
    finally:
        obs.set_recorder(old)
    c = rec.counters()
    assert c["plan.cache.miss"] == 1 and c["plan.cache.hit"] == 1
    names = {e["name"] for e in rec.events()}
    assert {"plan.build", "plan.schedule"} <= names
    build = next(e for e in rec.events() if e["name"] == "plan.build")
    assert build["args"]["B"] == 8
    d = t.describe()
    assert "counters" in d["obs"] and "spans" in d["obs"]


def test_local_batch_emits_executor_chunk_spans():
    import jax.numpy as jnp
    from repro.core import soft
    from repro.plan import transform
    t = transform.plan(8, impl="fused", V=2, tk=4)
    fhats = jnp.stack([jnp.asarray(soft.random_coeffs(8, seed=s))
                       for s in range(3)])
    rec = Recorder()
    old = obs.set_recorder(rec)
    try:
        t.inverse_batch(fhats)
    finally:
        obs.set_recorder(old)
    chunks = [e for e in rec.events() if e["name"] == "executor.chunk"]
    assert len(chunks) == 2                # 3 lanes on V=2 -> 2 launches
    assert [c["args"]["lanes"] for c in chunks] == [2, 1]
    assert all(c["args"]["mode"] == "local" and
               c["args"]["direction"] == "inverse" for c in chunks)


def test_tracing_is_bitwise_invisible_to_outputs():
    """Swapping recorders (or not recording at all) never changes
    transform numerics: spans wrap host dispatch only."""
    from repro.plan import transform
    t = transform.plan(8, impl="fused", V=2, tk=4)
    rng = np.random.default_rng(0)
    f = rng.normal(size=(16, 16, 16)) + 1j * rng.normal(size=(16, 16, 16))
    a = np.asarray(t.forward(f))
    old = obs.set_recorder(Recorder())
    try:
        b = np.asarray(t.forward(f))
    finally:
        obs.set_recorder(old)
    np.testing.assert_array_equal(a, b)


def test_service_stats_bounded_and_quantiled():
    import jax.numpy as jnp
    from repro.core import soft
    from repro.so3.service import SO3Service
    rec = Recorder(max_samples=64)
    old = obs.set_recorder(rec)
    try:
        svc = SO3Service(bandwidths=(8,), dtype=jnp.float64, lane_width=2)
        # fresh service: no latency block even if the recorder has samples
        rec.observe("service.latency_s", 123.0)
        assert "latency_s" not in svc.stats()
        rec.clear()
        z = soft.random_s2_coeffs(8, seed=0)
        futs = [svc.submit(z, z, refine=False) for _ in range(3)]
        svc.drain()
        for f in futs:
            assert f.result(timeout=120).index is not None
        st = svc.stats()
    finally:
        obs.set_recorder(old)
    assert st["completed"] == 3
    lat = st["latency_s"]
    assert set(lat) == {"mean", "p50", "p95", "p99", "max"}
    assert 0 < lat["p50"] <= lat["max"]
    # per-request spans + stage spans landed in the service's recorder
    names = {e["name"] for e in rec.events()}
    assert {"service.request", "service.pack", "service.launch",
            "service.refine"} <= names
    reqs = [e for e in rec.events() if e["name"] == "service.request"]
    assert len(reqs) == 3
    assert all(e["args"]["queue_wait_s"] >= 0 for e in reqs)
    # storage is the bounded ring, not a per-request list
    assert rec.quantiles("service.latency_s")["count"] == 3


CORRELATE_SPANS = ("correlate.pair", "correlate.dispatch", "correlate.wait",
                   "correlate.readback")


def _engine_events(served: bool):
    """Three (f, g) pairs at B = 8 on two lanes -- two launches, of 2 and
    1 lanes -- through the engine alone or through the service."""
    import jax.numpy as jnp
    from repro.core import soft
    from repro.so3 import CorrelationEngine
    from repro.so3.service import SO3Service

    pairs = [(soft.random_s2_coeffs(8, seed=s), soft.random_s2_coeffs(
        8, seed=s + 10)) for s in range(3)]
    rec = Recorder()
    old = obs.set_recorder(rec)
    try:
        if served:
            svc = SO3Service(bandwidths=(8,), dtype=jnp.float64,
                             lane_width=2)
            futs = [svc.submit(f, g) for f, g in pairs]
            svc.drain()
            for fu in futs:
                fu.result(timeout=120)
        else:
            eng = CorrelationEngine(8, dtype=jnp.float64, lane_width=2)
            for n0 in (0, 2):
                eng.match_batch(*zip(*pairs[n0:n0 + 2]))
    finally:
        obs.set_recorder(old)
    return rec


@pytest.mark.parametrize("served", [False, True])
def test_engine_spans_split_each_launch(served):
    rec = _engine_events(served)
    evs = rec.events()
    for name in CORRELATE_SPANS:
        spans = [e for e in evs if e["name"] == name]
        assert [e["args"]["lanes"] for e in spans] == [2, 1], name
    # the copy is the real lanes' grids, complex128 at f64: 16 bytes each
    q = rec.quantiles("correlate.readback_bytes")
    assert q["count"] == 2 and q["total"] == 3 * 16 ** 3 * 16
    if not served:
        return
    launches = [e for e in evs if e["name"] == "service.launch"]
    assert len(launches) == 2
    for e in evs:
        if e["name"] in CORRELATE_SPANS + ("executor.chunk",):
            assert sum(L["tid"] == e["tid"] and L["ts"] <= e["ts"] and
                       e["ts"] + e["dur"] <= L["ts"] + L["dur"]
                       for L in launches) == 1, e


def test_request_spans_carry_identity_and_end_at_the_answer():
    rec = _engine_events(served=True)
    evs = rec.events()
    by_launch = {}
    for e in evs:
        if e["name"].startswith("service.") and e["name"] != \
                "service.request":
            by_launch.setdefault(e["args"]["launch"], {})[e["name"]] = e
    assert len(by_launch) == 2
    assert all(set(v) == {"service.pack", "service.launch",
                          "service.refine"} for v in by_launch.values())
    reqs = sorted((e for e in evs if e["name"] == "service.request"),
                  key=lambda e: e["args"]["seq"])
    assert [e["args"]["seq"] for e in reqs] == [1, 2, 3]
    first, second = sorted(by_launch)
    assert [e["args"]["launch"] for e in reqs] == [first, first, second]
    for e in reqs:
        refine = by_launch[e["args"]["launch"]]["service.refine"]
        assert e["ts"] + e["dur"] >= refine["ts"] + refine["dur"]
    # the latency histogram reads the same interval as the request span
    lat = rec.quantiles("service.latency_s")
    assert lat["count"] == 3
    assert lat["total"] * 1e6 == pytest.approx(sum(e["dur"] for e in reqs))
