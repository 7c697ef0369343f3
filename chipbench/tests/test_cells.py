"""A cell is data: a configuration, a traffic mix and limits added as files
under a fresh checkout become a runnable cell, with no code edited."""

import json
import shutil
from pathlib import Path

import jax

from chipbench import cells, run
from chipbench.tests.smoke import LIMITS, SMOKE, TRAFFIC

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def test_added_files_make_a_runnable_cell(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "tiny-mla", "source": "https://huggingface.co/openbmb/MiniCPM3-4B",
         "file": "chipbench/configs/tiny-mla.json", "reduced": [],
         "why": "smoke"})
    manifest["workloads"].append(
        {"name": "tiny-mla.train.s64", "config": "tiny-mla",
         "traffic": "train.s64.b2", "chips": 1, "why": "smoke"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    base = tmp_path / "chipbench"
    conf = json.loads((base / "configs" / "minicpm3-4b.json").read_text())
    conf.update(SMOKE["minicpm3-4b"])
    (base / "configs" / "tiny-mla.json").write_text(json.dumps(conf))
    shutil.copy(base / "configs" / "minicpm3-4b.py",
                base / "configs" / "tiny-mla.py")
    (base / "traffic" / "train.s64.b2.json").write_text(json.dumps(TRAFFIC))
    (base / "limits" / "tiny-mla.train.s64.json").write_text(
        json.dumps({"limits": LIMITS}))

    cell = cells.load_cell("tiny-mla.train.s64", tmp_path)
    assert cell.chips == 1 and cell.traffic["seq_len"] == 64
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "train_step_ms_p90", "peak_hbm_gib", "setup_s"}
    assert "collective_exposed_ms.mesh" not in {m["name"]
                                               for m in cell.per_layer}
    res = run.execute(cell, 2**31 + 21, 0.5, False, jax.devices()[:1])
    assert res["correct"], res["checks"]
    assert res["attempted"] >= TRAFFIC["min_window_steps"]
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "checks"


def test_every_manifest_cell_loads():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = cells.load_cell(w["name"])
        assert set(cell.limits) == {"loss_gap", "grad_gap", "update_gap"}
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(cells.CellError):
        cells.peaks("cpu")
    assert cells.peaks("TPU v5 lite")["bf16_flops"] == 197e12
