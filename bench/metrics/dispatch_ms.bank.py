"""Host time per completed bank query spent enqueueing it (one launch of
the jitted loop over the bank's chunks; the device's work is waited for
afterwards, in ``correlate.wait``): the program's ``correlate.dispatch``
spans over the window, divided by the queries completed."""


def read(run):
    q = run.obs.get("correlate.dispatch")
    done = run.counters.get("completed", 0)
    return q["total"] * 1e3 / done if q and done else None
