"""In-program spans and compile counters of ``Trainer.fit``.

``span(name)`` times one piece of the host loop twice: as a
``jax.profiler.TraceAnnotation``, which lands in any profiler trace on the
profiler's clock beside the device operations, and as a
``(name, step, start_ns, end_ns)`` record on ``time.perf_counter_ns`` in the
current fit's record.  The recorder is always on; outside a ``fit()`` a span
is only the annotation.

A ``jax.monitoring`` listener, registered at import, counts compile events.
Each backend compile (``backend_compile_duration``, which also wraps a load
from the persistent compilation cache) is counted against the innermost
open span of the current fit.  The seconds of tracing, lowering and backend
compiling are summed for the whole process; nested events (a jitted
function traced inside another's trace) are charged their self time, so the
seconds add up to the time spent compiling.  The recorder serves the thread
that runs ``fit``: a compile on another thread during a fit is charged to
that fit.

``fits()`` gives the last ``MAX_FITS`` records, oldest first; each keeps its
last ``MAX_SPANS`` spans and its first ``MAX_EVENTS`` compile events.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import NamedTuple

import jax
from jax import monitoring

MAX_FITS = 8
MAX_SPANS = 8 * 4096        # a fit's last 4096 steps at up to 8 spans each
MAX_EVENTS = 4096

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_SPANS = (BACKEND_COMPILE,
                 "/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")


class Span(NamedTuple):
    name: str
    step: int | None
    start_ns: int
    end_ns: int


class CompileEvent(NamedTuple):
    end_ns: int
    seconds: float       # self time: nested compile events left out


@dataclasses.dataclass
class Record:
    """One ``fit``."""
    compile_s_before: float = 0.0   # process compile seconds when it began
    steps: int = 0                  # steps completed (rows logged)
    spans: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=MAX_SPANS))
    # innermost open span (None between spans): backend compiles
    compiles: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    events: list = dataclasses.field(default_factory=list)  # first ones
    _open: list = dataclasses.field(default_factory=list)
    _step: int | None = None


_fits: collections.deque = collections.deque(maxlen=MAX_FITS)
_current: Record | None = None
_compile_s = 0.0                         # process-wide, every record
_stack: collections.deque = collections.deque(maxlen=256)  # (start, dur)
_CLOCK_OFFSET_NS = time.perf_counter_ns() - time.time_ns()


class span:
    """``with span(name) as s:`` — ``s.start_ns`` / ``s.end_ns`` after."""
    __slots__ = ("name", "rec", "ann", "start_ns", "end_ns")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.rec = _current
        self.start_ns = time.perf_counter_ns()
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        if self.rec is not None:
            self.rec._open.append(self.name)
        return self

    def __exit__(self, *exc):
        rec = self.rec
        if rec is not None:
            rec._open.pop()
        self.ann.__exit__(*exc)
        self.end_ns = time.perf_counter_ns()
        if rec is not None:
            rec.spans.append(Span(self.name, rec._step, self.start_ns,
                                  self.end_ns))
        return False


class step:
    """One iteration of the loop: a ``StepTraceAnnotation("train")``, and
    the spans inside it carry its step number."""
    __slots__ = ("num", "ann")

    def __init__(self, step_num: int):
        self.num = step_num
        self.ann = jax.profiler.StepTraceAnnotation("train",
                                                    step_num=step_num)

    def __enter__(self):
        if _current is not None:
            _current._step = self.num
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        return False


class fit:
    """``with fit() as rec:`` opens a new record; it is ``fits()[-1]``."""
    __slots__ = ("rec", "prev")

    def __enter__(self) -> Record:
        global _current
        self.rec = Record(compile_s_before=_compile_s)
        _fits.append(self.rec)
        self.prev, _current = _current, self.rec
        return self.rec

    def __exit__(self, *exc):
        global _current
        _current = self.prev
        return False


def fits() -> list[Record]:
    """The last ``MAX_FITS`` fit records, oldest first."""
    return list(_fits)


def last_fit(steps: int) -> Record:
    """The newest fit record, which must hold ``steps`` completed steps (a
    reader's check that it reads the fit it was meant to)."""
    rec = _fits[-1]
    if rec.steps != steps:
        raise ValueError(f"the last fit recorded {rec.steps} steps, "
                         f"expected {steps}")
    return rec


def _on_time_span(event, start_s, end_s, **kw):
    global _compile_s
    if event not in COMPILE_SPANS:
        return
    start = int(start_s * 1e9) + _CLOCK_OFFSET_NS
    end = int(end_s * 1e9) + _CLOCK_OFFSET_NS
    inner = 0
    while _stack and _stack[-1][0] >= start:
        inner += _stack.pop()[1]
    _stack.append((start, end - start))
    seconds = max(0, end - start - inner) / 1e9
    _compile_s += seconds
    rec = _current
    if rec is None:
        return
    if event == BACKEND_COMPILE:
        rec.compiles[rec._open[-1] if rec._open else None] += 1
    if len(rec.events) < MAX_EVENTS:
        rec.events.append(CompileEvent(end, seconds))


monitoring.register_event_time_span_listener(_on_time_span)
