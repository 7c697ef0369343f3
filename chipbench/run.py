#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU this process finds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell comes from ``BENCHMARK.json``; its configuration, traffic, limits
and per-layer metric readers are files under ``chipbench/`` found by name
(``chipbench/cells.py``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of a shorter window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number that decides ``correct``
with its limit.  The same numbers end standard error.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 1.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

import psutil

T_START = psutil.Process().create_time()
ROOT = Path(__file__).resolve().parents[1]


class NoChip(Exception):
    pass


def chips(n: int) -> list:
    """The first ``n`` TPU devices; ``NoChip`` if there are not so many."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: the first device is {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def result(cell, out: dict, devices, traced: bool) -> dict:
    from chipbench import cells, check
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    res = {}
    if traced:
        ctx = out["ctx"]
        ctx["peaks"] = cells.peaks(dev.device_kind)
        tr = ctx["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"], ROOT)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        res["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": units[m["name"]]}
    checks = out["checks"]
    ok = (check.passed(checks) and out["failed"] == 0
          and all(math.isfinite(v["value"]) for v in metrics.values()))
    head = {"correct": ok, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    head.update(res)
    head["checks"] = checks
    return head


def execute(cell, seed: int, seconds: float, traced: bool, devices) -> dict:
    """One run of ``cell`` on ``devices``: the result's JSON object."""
    from chipbench import train
    out = train.run(cell, seed, seconds, traced, T_START, devices)
    return result(cell, out, devices, traced)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import cells
    cell = cells.load_cell(args.workload, ROOT)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"chipbench: {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}", file=sys.stderr, flush=True)

    res = execute(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
