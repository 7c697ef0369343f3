"""host_gap_ms: the host's work between two steps, while the device idles.

Median, over the window's steps k >= 1, of end(``fit.step`` k) minus
end(``fit.sync`` k-1): from the host seeing step k-1's metrics to its
having enqueued step k (the log row, a checkpoint, the next batch and the
dispatch).  Read from the program's span recorder (``repro.launch.spans``),
whose last fit is the window; nothing where the program has no recorder."""

import importlib.util
import statistics


def read(ctx):
    if importlib.util.find_spec("repro.launch.spans") is None:
        return None         # a program without the span recorder
    from repro.launch import spans
    rec = spans.last_fit(ctx["trace"]["steps"])
    step_end = {s.step: s.end_ns for s in rec.spans if s.name == "fit.step"}
    sync_end = {s.step: s.end_ns for s in rec.spans if s.name == "fit.sync"}
    gaps = [end - sync_end[k - 1] for k, end in step_end.items()
            if k - 1 in sync_end]
    if not gaps:
        raise ValueError("no two consecutive steps recorded")
    return statistics.median(gaps) / 1e6
