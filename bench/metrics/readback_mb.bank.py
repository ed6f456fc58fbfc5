"""Megabytes (1e6 bytes) copied from the device per completed bank query:
the program's ``correlate.readback_bytes`` observations over the window,
divided by the queries completed."""


def read(run):
    q = run.obs.get("correlate.readback_bytes")
    done = run.counters.get("completed", 0)
    return q["total"] / 1e6 / done if q and done else None
