"""Device time per completed bank query of the peak search: the
``grid_peaks`` kernel's events over the window, divided by the queries
completed.  A time and not a share of the HBM roofline: the compiler keeps
each chunk's real grids (64 MiB at B = 64, V = 8) in the chip's VMEM, so
the kernel reads them faster than HBM could deliver them."""
from bench import kernels, opnames

KERNEL = "grid_peaks"


def read(run):
    t = run.trace
    done = run.counters.get("completed", 0)
    if t is None or not done:
        return None
    sel = opnames.named(t.ops, (KERNEL,))
    return 1e3 * kernels.busy_seconds(sel) / done if sel else None
