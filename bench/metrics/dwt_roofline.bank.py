"""Share of the roofline reached by the iDWT kernels of the window's bank
queries: the least time for the templates' inverses (padded lanes are not
work) over the device time of the iDWT events.  The events are picked by
their instruction's name (``bench.kernels.DWT_NAMES``) alone: a bank chunk
also runs ``grid_peaks``, another Pallas kernel."""
from bench import kernels, opnames, workcount


def read(run):
    t = run.trace
    templates = run.counters.get("templates", 0)
    if t is None or not templates:
        return None
    sel = opnames.named(t.ops, kernels.DWT_NAMES)
    if not sel:
        return None
    least, _ = workcount.least_seconds(run.config["B"], templates, "inverse",
                                       run.device_kind)
    return 100.0 * least / kernels.busy_seconds(sel)
