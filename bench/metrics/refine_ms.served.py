"""Host peak finding and refinement per request: the program's
``service.refine`` spans over the window, divided by the requests
completed."""


def read(run):
    q = run.obs.get("service.refine")
    done = run.counters.get("completed", 0)
    return q["total"] * 1e3 / done if q and done else None
