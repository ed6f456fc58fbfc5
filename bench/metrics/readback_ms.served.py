"""Host time per launch spent copying the grids to the host and
conjugating them there: the program's ``correlate.readback`` spans over the
window, divided by the launches."""


def read(run):
    q = run.obs.get("correlate.readback")
    launches = run.counters.get("launches", 0)
    return q["total"] * 1e3 / launches if q and launches else None
