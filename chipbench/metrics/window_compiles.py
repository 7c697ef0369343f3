"""window_compiles: compile events charged to the window's ``fit.step``
spans: backend compiles, loads from the persistent compilation cache
included.  A steady window reads 0.

Read from the program's compile counters (``repro.launch.spans``), whose
last fit is the window; nothing where the program has no recorder."""

import importlib.util


def read(ctx):
    if importlib.util.find_spec("repro.launch.spans") is None:
        return None         # a program without the span recorder
    from repro.launch import spans
    rec = spans.last_fit(ctx["trace"]["steps"])
    return rec.compiles["fit.step"]
