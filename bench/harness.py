"""Runs one cell once: set-up, the measured window, the check of what the
window produced, and the metrics.

The order is fixed by what each step may disturb: set-up ends at the first
timed step; the profiler (``--trace 1`` only) covers exactly the window;
the device's memory peak is read before the program's state is freed;
and the reference runs last, on the host, so it sets no peak and counts
in no time that is reported.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import trace as tr
from .loader import Catalog

WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    workload: str
    config: dict
    device_kind: str
    setup_s: float
    window_s: float
    steps: int = 0                   # closed loop: steps completed
    latencies_s: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    obs: dict = dataclasses.field(default_factory=dict)
    trace: "tr.Reduced | None" = None


def process_start_perf() -> float:
    """perf_counter() reading at the moment this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


def enable_compile_cache() -> str:
    """The program's persistent compilation cache: ``.jax_cache/`` at the
    checkout's root, or where $JAX_COMPILATION_CACHE_DIR says.  Every
    compile is kept, not only those over a second: the service's warm-up
    is many small ones (a cached served set-up took 17 to 22 s with this,
    25 to 31 s without, on a TPU v5e)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class _CompileCounter:
    """Counts XLA backend compiles while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             log=sys.stderr) -> dict:
    """One run of one cell; returns the result object of the last line."""
    import jax
    from repro import obs

    cat = Catalog(root)
    wl = cat.workload(workload)
    config = cat.config(wl["config"])
    mix = cat.traffic(wl["traffic"])
    device = device_info(wl["chips"], require_chip)
    cache = enable_compile_cache()
    print(f"compile cache: {cache}; process start to the driver's set-up: "
          f"{time.perf_counter() - t_start:.3f} s", file=log, flush=True)

    driver = cat.driver(config["driver"])(config, mix, seed, log=log)
    driver.setup()
    counter = _CompileCounter()
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        os.environ["REPRO_OBS_JAX_TRACE"] = "1"
        # host spans come from annotations and the runtime; tracing every
        # Python call would slow the host it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
    recorder = obs.get_recorder()
    recorder.clear()                     # spans and histograms of the window
    counter.on = True
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            win = driver.window(seconds)
    finally:
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
    obs_summary = recorder.summary()
    host_spans = recorder.events() if trace else []
    device["memory_peak_bytes"] = memory_peak(wl["chips"])
    driver.collect()
    print(f"compiles in the window: {counter.n}", file=log, flush=True)

    reduced = None
    if trace:
        reduced = tr.reduce(_xplane(tdir), WINDOW_SPAN, host_spans)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    checks = driver.check()
    run = Run(workload=workload, config=config,
              device_kind=device["kind"], setup_s=setup_s,
              window_s=win["window_s"], steps=win.get("steps", 0),
              latencies_s=win.get("latencies_s", []),
              counters=win.get("counters", {}), obs=obs_summary,
              trace=reduced)
    metrics = {}
    for m in cat.metrics(workload, per_layer=trace):
        value = cat.reader(m["name"])(run)
        if value is None:
            print(f"metric {m['name']}: nothing to read in this run, left "
                  f"out", file=log, flush=True)
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = win["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = {c["name"]: {"value": float(c["value"]),
                                    "limit": c["limit"]} for c in checks}
    return result


def _xplane(tdir: str) -> str:
    found = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    return found[0]


def print_result(result: dict, out=sys.stdout, log=sys.stderr) -> None:
    """The checks as the last lines of stderr, the result as the last line
    of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=log, flush=True)
    print(json.dumps(result), file=out, flush=True)
