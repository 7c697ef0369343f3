"""Find a cell and everything it names, by name, from data files.

``BENCHMARK.json`` (at the root of the checkout) lists the cells: each names
a configuration, a traffic mix and a chip count.  Everything else is a file
found by name:

    chipbench/configs/<config>.json   sizes, source, cuts (the file as run)
    chipbench/configs/<config>.py     its plain reference and FLOPs per token
    chipbench/traffic/<traffic>.json  the traffic mix
    chipbench/limits/<cell>.json      the limits that decide ``correct``
    chipbench/metrics/<metric>.py     the reader of one per-layer metric

So a later change adds a configuration, a mix, a cell or a metric by adding
files and entries, and edits none of these modules.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent


class CellError(Exception):
    """The manifest or a file it names is missing or malformed."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    model: ModuleType     # configs/<config>.py
    traffic: dict         # traffic/<traffic>.json
    limits: dict          # limits/<name>.json: what decides ``correct``
    end_to_end: list      # manifest entries this cell reports
    per_layer: list       # manifest entries this cell reports when traced


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise CellError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"no file {path}")
    return json.loads(path.read_text())


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path | None = None) -> Cell:
    """The cell called ``name`` in ``<root>/BENCHMARK.json``; its files are
    looked up under ``<root>/chipbench``."""
    root = root or HERE.parent
    manifest = _json(root / "BENCHMARK.json")
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise CellError(f"no single workload {name!r} in BENCHMARK.json")
    w = entries[0]
    base = root / "chipbench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(base / "configs" / f"{w['config']}.json"),
        model=load_module(base / "configs" / f"{w['config']}.py"),
        traffic=_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=_json(base / "limits" / f"{name}.json")["limits"],
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )


def metric_reader(name: str, root: Path | None = None):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``."""
    root = root or HERE.parent
    return load_module(root / "chipbench" / "metrics" / f"{name}.py").read


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of this kind; an unknown kind is an error."""
    table = _json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise CellError(f"device kind {device_kind!r} is not in peaks.json")
    return table["devices"][device_kind]
