"""Host time per launch spent packing the pair coefficients and enqueueing
the inverse: the program's ``correlate.pair`` and ``correlate.dispatch``
spans over the window, divided by the launches."""


def read(run):
    spans = [run.obs.get(n) for n in ("correlate.pair", "correlate.dispatch")]
    launches = run.counters.get("launches", 0)
    if not all(spans) or not launches:
        return None
    return sum(q["total"] for q in spans) * 1e3 / launches
