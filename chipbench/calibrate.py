#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds 12 \\
        --first-seed <n> [--faults 3] [--out <file.json>]

One process, one compiled step: for each seed it trains the cell's first
three steps through ``Trainer.fit`` (as a run's set-up does) and reads the
compared numbers against the float32 reference.  For the first ``--faults``
seeds it also reads, against the same reference:

* ``control``: the reference itself with every product's operands rounded
  to fp8 (e4m3, per-tensor scale), the precision below the configuration's
  bf16;
* ``half_batch``: the reference with half of the batch's tokens left out
  (the second half of the rows, or of the one row), the mean taken over the
  rest.  On a mesh with data parallelism this is also the gradient that one
  data shard computes when the exchange between chips is left out.

A state returned unchanged reads ``update_gap`` = 1 by definition and needs
no run.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def half_mask(batch: int, seq: int):
    import numpy as np
    keep = np.ones((batch, seq), bool)
    if batch > 1:
        keep[batch // 2:] = False
    else:
        keep[:, seq // 2:] = False
    return keep


def calibrate(cell, devices, n_seeds: int, first_seed: int, n_faults: int):
    """(rows of readings, one per seed; their summary): the largest program
    reading and the smallest control and fault readings of each number."""
    from chipbench import check, plain, train
    t = cell.traffic
    model = cell.model.model(cell.config)
    trainer = rec = None
    rows = []
    for i in range(n_seeds):
        seed = first_seed + i
        t0 = time.time()
        if trainer is None:
            trainer = train.build(cell, seed, devices)
            rec = train.StepRecorder(trainer.step_jit)
            trainer.step_jit = rec
        trainer.seed = seed
        rec.calls.clear()
        prog = train.program_readings(trainer, rec)
        batches = [plain.tokens(model.vocab, t["global_batch"], t["seq_len"],
                                s, seed) for s in range(train.CHECK_STEPS)]
        ref = plain.train(model, seed, batches, plain.Arith(), devices)
        row = {"seed": seed, "program": check.readings(prog, ref),
               "loss": prog["loss"], "ref_loss": ref["loss"]}
        if i < n_faults:
            ctl = plain.train(model, seed, batches, plain.Arith(fp8=True),
                              devices)
            row["control"] = check.readings(ctl, ref)
            half = plain.train(model, seed, batches, plain.Arith(), devices,
                               keep=half_mask(t["global_batch"],
                                              t["seq_len"]))
            row["half_batch"] = check.readings(half, ref)
        row["seconds"] = time.time() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in ("program", "control", "half_batch"):
        got = [r[kind] for r in rows if kind in r]
        pick = max if kind == "program" else min
        summary[kind] = {k: pick(g[k] for g in got) for k in got[0]}
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import cells, run
    cell = cells.load_cell(args.workload, ROOT)
    try:
        devices = run.chips(cell.chips)
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows, summary = calibrate(cell, devices, args.seeds, args.first_seed,
                              args.faults)
    print(json.dumps({"workload": cell.name, "device": devices[0].device_kind,
                      "largest_program": summary["program"],
                      "smallest_control": summary["control"],
                      "smallest_half_batch": summary["half_batch"]}),
          flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
