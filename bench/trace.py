"""Reduction of a profiler trace of the window to what the metrics read.

The JAX profiler writes an ``.xplane.pb``: planes (one per chip, one for
the host), lines, and events with a start and a duration in nanoseconds on
one clock.  The window is the host span ``bench.window`` that the harness
opens around it.  Of each chip's plane the line of XLA operations counts:

  busy_s     the union of the intervals in which an operation ran, inside
             the window, averaged over the chips
  ops        every operation inside the window, as (name, start, duration)
  top_ops    the ten operation names that took most device time
  idle_gaps  the ten longest gaps between operations, each labelled by the
             innermost host span open at its midpoint: the program's own
             spans (``repro.obs``) and the host events of the trace

Everything below the loader takes plain lists, so the tests check it on
small event lists.
"""
from __future__ import annotations

import dataclasses

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host events that are bookkeeping, not work the host was doing
HOST_NOISE = ("ThreadpoolListener",)
TOP_N = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: list                # [(name, start_ns, dur_ns)] of all chips
    top_ops: list
    idle_gaps: list


def load(path: str) -> tuple[dict, list]:
    """({chip plane: [(name, start_ns, dur_ns)] of its XLA ops},
    [(name, start_ns, dur_ns)] of host events)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                         for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.duration_ns > 0
                            and not e.name.startswith(HOST_NOISE))
    return chips, host


def window_of(host: list, name: str) -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in host if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return max(spans, key=lambda iv: iv[1] - iv[0])


def clip(events: list, t0: float, t1: float) -> list:
    """Events overlapping [t0, t1), cut to it."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def merge(events: list) -> list:
    """Union of event intervals as sorted disjoint (start, end) pairs."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [tuple(iv) for iv in out]


def top_ops(ops: list, n: int = TOP_N) -> list:
    tot = {}
    for name, _, d in ops:
        tot[name] = tot.get(name, 0.0) + d * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def label(host: list, t: float) -> str:
    """Innermost (shortest) host span open at time t."""
    open_ = [(d, name) for name, s, d in host if s <= t < s + d]
    return min(open_)[1] if open_ else "no host span"


def idle_gaps(busy: list, t0: float, t1: float, host: list,
              n: int = TOP_N) -> list:
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(host, (a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:n]]


def reduce_events(chips: dict, host: list, window_span: str,
                  extra_host: list = ()) -> Reduced:
    t0, t1 = window_of(host, window_span)
    host = [e for e in host if e[0] != window_span] + list(extra_host)
    ops, busy_total, first_busy = [], 0.0, None
    for plane in sorted(chips):
        clipped = clip(chips[plane], t0, t1)
        busy = merge(clipped)
        busy_total += sum(b - a for a, b in busy)
        ops.extend(clipped)
        if first_busy is None:
            first_busy = busy
    n = max(len(chips), 1)
    return Reduced(window_s=(t1 - t0) * 1e-9, busy_s=busy_total / n * 1e-9,
                   ops=ops, top_ops=top_ops(ops),
                   idle_gaps=idle_gaps(first_busy or [], t0, t1, host))


def recorder_spans(events: list, t_ns: float) -> list:
    """The program's ``repro.obs`` spans, recorded from a Recorder cleared
    at the window's start, on the trace's clock (window start = t_ns)."""
    return [(e["name"], t_ns + e["ts"] * 1e3, e["dur"] * 1e3)
            for e in events if e.get("ph") == "X" and e.get("dur", 0) > 0]


def reduce(path: str, window_span: str, obs_events: list = ()) -> Reduced:
    chips, host = load(path)
    t0, _ = window_of(host, window_span)
    return reduce_events(chips, host, window_span,
                         recorder_spans(list(obs_events), t0))


def op_seconds(ops: list, match) -> tuple[float, int]:
    """Device seconds and count of the operations whose name ``match``es."""
    sel = [d for name, _, d in ops if match(name)]
    return sum(sel) * 1e-9, len(sel)
