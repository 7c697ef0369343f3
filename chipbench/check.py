"""The numbers that decide ``correct``, each against its limit.

The program's and the reference's readings of the same three steps:

* ``loss_gap``: the largest |loss - reference loss| / reference loss over
  the steps;
* ``grad_gap``: over the leaves, the largest gap between the norm of the
  program's first gradient (as AdamW got it) and the reference's, over the
  larger of that leaf's reference norm and the median leaf's;
* ``update_gap``: the same for the change of each leaf over the steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move by round-off alone and are left out.

A missing leaf, or a number that is not finite, reads as infinity.
"""

from __future__ import annotations

import math
import statistics

NEGLIGIBLE = 1e-3


def _gap(prog: dict, ref: dict, keep=None) -> float:
    if set(prog) != set(ref):
        return math.inf
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys)
    worst = 0.0
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        worst = max(worst, g if math.isfinite(g) else math.inf)
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """{name: value} of the compared numbers."""
    gaps = [abs(p - r) / abs(r)
            for p, r in zip(prog["loss"], ref["loss"], strict=True)]
    loss_gap = max(gaps) if all(map(math.isfinite, gaps)) else math.inf
    g_med = statistics.median(ref["grad_norm"].values())
    moving = {k for k, v in ref["grad_norm"].items()
              if v >= NEGLIGIBLE * g_med}
    out = {"loss_gap": loss_gap,
           "grad_gap": _gap(prog["grad_norm"], ref["grad_norm"]),
           "update_gap": _gap(prog["update_norm"], ref["update_norm"],
                              moving)}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """{name: {"value": reading, "limit": limit}} for every limit."""
    got = readings(prog, ref)
    return {k: {"value": got[k], "limit": limits[k]} for k in limits}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
