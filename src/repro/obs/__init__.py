"""repro.obs -- unified tracing/metrics layer (spans, counters, bounded
histograms, Chrome-trace export).  See :mod:`repro.obs.trace`."""
from .trace import (Recorder, add_span, check_chrome_trace, counter,
                    get_recorder, inc, observe, set_recorder, span, time_fn)

__all__ = ["Recorder", "span", "add_span", "inc", "observe", "counter",
           "time_fn", "get_recorder", "set_recorder", "check_chrome_trace"]
