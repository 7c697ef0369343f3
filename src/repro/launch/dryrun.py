import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the platform
# and device count at first init).  512 host devices back the production
# meshes: single-pod (16,16)=256 and multi-pod (2,16,16)=512.  The dry run
# is a CPU tool: pinning the platform keeps it (and the children that
# benchmarks/roofline.py starts) off an accelerator another process holds.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402

from repro.configs import all_cells, cell_status  # noqa: E402
from repro.launch.cell import lower_cell                     # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             variant: dict | None = None, tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    status = cell_status(arch, shape)
    key = f"{arch}__{shape}__{mesh_name}{tag}"
    if status != "run":
        row = dict(arch=arch, shape=shape, mesh=mesh_name, skipped=status)
        _write(out_dir, key, row)
        print(f"SKIP {key}: {status}")
        return row

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    res, compiled = lower_cell(arch, shape, mesh, variant)
    row = res.to_json()
    row["mesh"] = mesh_name
    row["wall_s"] = time.time() - t0
    row["variant"] = variant or {}
    _write(out_dir, key, row)
    if res.error:
        print(f"FAIL {key}: {res.error[:300]}")
    else:
        print(f"OK   {key}: flops={res.flops:.4g} hlo_bytes={res.hlo_bytes:.4g} "
              f"coll={res.collective_total / 1e9:.2f}GB "
              f"peak/dev={res.peak_bytes_per_device / 2**30:.2f}GiB "
              f"compile={res.compile_s:.0f}s")
        if compiled is not None:
            print(f"     memory_analysis: args={res.argument_bytes/2**30:.2f}GiB "
                  f"temp={res.temp_bytes/2**30:.2f}GiB "
                  f"out={res.output_bytes/2**30:.2f}GiB | "
                  f"cost_analysis: flops={res.flops:.4g}")
    sys.stdout.flush()
    return row


def _write(out_dir: str, key: str, row: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, key + ".json"), "w") as f:
        json.dump(row, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--variant", default="",
                    help="JSON dict of perf-iteration knobs")
    ap.add_argument("--tag", default="", help="suffix for output files")
    args = ap.parse_args()

    variant = json.loads(args.variant) if args.variant else None
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = [(a, s) for a, s, _ in all_cells()
             if args.arch in ("all", a) and args.shape in ("all", s)]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            row = run_cell(arch, shape, mp, args.out, variant, args.tag)
            if row.get("error"):
                failures += 1
    print(f"dry-run complete: {len(cells) * len(meshes)} cells, "
          f"{failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
