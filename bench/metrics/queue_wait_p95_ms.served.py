"""95th percentile of the service's queue wait (submit to the launch of
the request's group): the program's ``service.queue_wait_s`` histogram,
recorded over the window."""


def read(run):
    q = run.obs.get("service.queue_wait_s")
    return q["p95"] * 1e3 if q else None
