"""90th percentile of the client latency of the requests due in the
window, from when each was due to when its answer arrived."""
import numpy as np


def read(run):
    lat = run.latencies_s
    return float(np.percentile(lat, 90) * 1e3) if lat else None
