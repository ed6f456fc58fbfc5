"""Device time per roundtrip of XLA's part of the grid stages: the FFTs,
the gathers and scatters between grid and clusters, and layout copies;
every moment the device was busy with anything but the DWT kernels."""
from bench import kernels


def read(run):
    t = run.trace
    if t is None or not run.steps:
        return None
    secs, n = kernels.grid_seconds(t.ops)
    return 1e3 * secs / run.steps if n else None
