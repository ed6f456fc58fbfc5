"""Host time per launch spent blocked on the device once the inverse is
enqueued: the program's ``correlate.wait`` spans over the window, divided
by the launches."""


def read(run):
    q = run.obs.get("correlate.wait")
    launches = run.counters.get("launches", 0)
    return q["total"] * 1e3 / launches if q and launches else None
