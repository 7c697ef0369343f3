"""Reduce a profiler trace of the measured window to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a plain
event list; ``reduce`` works on that list only, so a small recorded list
(``tests/data/``) checks it without a chip.

The window runs from the start of the first ``dispatch`` span (the harness's
mark around each call of the jitted step) to the end of the ``window`` span
(around the measured ``fit``).  Per device, busy time is the union of the
intervals in which an operation ran; a collective is exposed where no other
operation on that device overlaps it.
"""

from __future__ import annotations

import re
from pathlib import Path

COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|all-to-all")
# an operation that holds others (a loop, a branch) is not counted itself:
# the operations inside it appear in the trace on their own
CONTAINER = {"while", "conditional", "call"}
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPANS = ("dispatch", "window")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def parse_op(text: str) -> tuple[str, str]:
    """(short name, opcode) of a TPU trace's operation, whose event name is
    the HLO instruction: ``%fusion.7 = (bf16[..]{layout}, ..) fusion(..)``.
    The short name keeps the instruction's name, opcode and result shape."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:160], text
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, after = rest[:i + 1], rest[i + 1:].strip()
    else:
        typ, _, after = rest.partition(" ")
    opcode = after.split("(", 1)[0].strip()
    short = f"{name.lstrip('%')} {opcode} {_LAYOUT.sub('', typ)}"
    return short[:160], opcode


def load(trace_dir: Path) -> dict:
    """{"ops": {device: [[name, opcode, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]} from the newest xplane file
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops: dict = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(m.group(1), []).extend(
                    [*parse_op(e.name), e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                             for e in line.events if e.name in SPANS)
    return {"ops": ops, "spans": spans}


def _union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip(ops, lo, hi):
    """Operations inside [lo, hi], cut to it; containers left out."""
    return [[n, op, max(s, lo), min(e, hi)] for n, op, s, e in ops
            if e > lo and s < hi and op not in CONTAINER]


def is_collective(name: str, opcode: str) -> bool:
    return bool(COLLECTIVE.search(opcode) or COLLECTIVE.search(name.split()[0]))


def reduce(events: dict, n_devices: int, top: int = 10) -> dict:
    """Window length, busy time per device, the busiest device's idle share
    and exposed collective time, its longest operations and idle gaps."""
    dispatch = sorted(s for s in events["spans"] if s[0] == "dispatch")
    window = [s for s in events["spans"] if s[0] == "window"]
    if not dispatch or not window:
        raise ValueError("trace holds no dispatch or window span")
    lo, hi = dispatch[0][1], max(s[2] for s in window)
    span_ns = hi - lo
    devices = sorted(events["ops"], key=int)[:n_devices]
    if not devices:
        raise ValueError("trace holds no device operations")
    per = {}
    for d in devices:
        ops = _clip(events["ops"][d], lo, hi)
        busy = _union([s, e] for _, _, s, e in ops)
        coll = _union([s, e] for n, op, s, e in ops if is_collective(n, op))
        comp = _union([s, e] for n, op, s, e in ops
                      if not is_collective(n, op))
        per[d] = {"ops": ops, "busy": busy, "busy_ns": _length(busy),
                  "exposed_ns": _length(coll) - _overlap(coll, comp)}
    d = max(per, key=lambda k: per[k]["busy_ns"])
    busiest = per[d]

    totals: dict = {}
    for n, _, s, e in busiest["ops"]:
        totals[n] = totals.get(n, 0) + (e - s)
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    gaps = []
    edges = [[lo, lo]] + busiest["busy"] + [[hi, hi]]
    for (_, e0), (s1, _) in zip(edges, edges[1:], strict=False):
        if s1 > e0:
            mid = (e0 + s1) / 2
            inside = any(s <= mid <= e for _, s, e in dispatch)
            gaps.append(("dispatch" if inside else "host loop", s1 - e0))
    gaps.sort(key=lambda g: -g[1])

    return {
        "window_s": span_ns / 1e9,
        "busy_s": sum(p["busy_ns"] for p in per.values()) / len(per) / 1e9,
        "idle_pct": 100.0 * (1 - busiest["busy_ns"] / span_ns),
        "collective_exposed_s": busiest["exposed_ns"] / 1e9,
        "steps": len(dispatch),
        "busiest_device": d,
        "device_ops": [[n, t / 1e9] for n, t in device_ops],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps[:top]],
    }
