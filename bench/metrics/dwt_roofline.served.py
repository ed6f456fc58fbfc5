"""Share of the roofline reached by the iDWT kernel of the window's
launches: the least time for the real requests' work (padded lanes are
not work) over the device time of the kernel events."""
from bench import kernels, workcount


def read(run):
    t = run.trace
    lanes = run.counters.get("transforms", 0)
    if t is None or not lanes:
        return None
    secs, n = kernels.dwt_seconds(t.ops)
    if not n:
        return None
    least, _ = workcount.least_seconds(run.config["B"], lanes, "inverse",
                                       run.device_kind)
    return 100.0 * least / secs
