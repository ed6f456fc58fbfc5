"""Share of the roofline reached by the fused DWT and iDWT kernels of the
window's roundtrips: the least time the chip could take for their work
(``bench.workcount``) over the device time of the kernel events."""
from bench import kernels, workcount


def read(run):
    t = run.trace
    if t is None or not run.steps:
        return None
    secs, n = kernels.dwt_seconds(t.ops)
    if not n:
        return None
    B = run.config["B"]
    least = sum(workcount.least_seconds(B, run.steps, d, run.device_kind)[0]
                for d in workcount.DIRECTIONS)
    return 100.0 * least / secs
