"""host_data_ms: median duration of ``fit.data`` over the window: making the
step's batch (``data/pipeline``) and copying it to the device.

Read from the program's span recorder (``repro.launch.spans``), whose last
fit is the window; nothing where the program has no recorder."""

import importlib.util
import statistics


def read(ctx):
    if importlib.util.find_spec("repro.launch.spans") is None:
        return None         # a program without the span recorder
    from repro.launch import spans
    rec = spans.last_fit(ctx["trace"]["steps"])
    return statistics.median(s.end_ns - s.start_ns for s in rec.spans
                             if s.name == "fit.data") / 1e6
