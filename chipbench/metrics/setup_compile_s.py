"""setup_compile_s: seconds of compile events (tracing, lowering, backend
compiles with their persistent-cache loads) from the recorder's import,
before the harness builds the trainer, to the start of the window's first
``fit.step``; the part of ``setup_s`` spent compiling.

Read from the program's compile counters (``repro.launch.spans``), whose
last fit is the window; nothing where the program has no recorder."""

import importlib.util


def read(ctx):
    if importlib.util.find_spec("repro.launch.spans") is None:
        return None         # a program without the span recorder
    from repro.launch import spans
    rec = spans.last_fit(ctx["trace"]["steps"])
    if len(rec.spans) == spans.MAX_SPANS or \
            len(rec.events) == spans.MAX_EVENTS:
        raise ValueError("the window's first steps are no longer recorded")
    first = min(s.start_ns for s in rec.spans if s.name == "fit.step")
    return rec.compile_s_before + sum(e.seconds for e in rec.events
                                      if e.end_ns <= first)
