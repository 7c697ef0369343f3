"""Smoke-size cells for the CPU tests: the benchmark's configurations with
every width cut down, run through the same harness code."""

from __future__ import annotations

import copy
import json
from dataclasses import replace
from pathlib import Path

from chipbench import cells

BENCH = Path(__file__).resolve().parents[1]

SMOKE = {
    "minicpm3-4b": {
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "vocab_size": 256, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8},
    "mamba2-1.3b": {
        "d_model": 64, "n_layer": 2, "vocab_size": 256,
        "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                    "expand": 2, "headdim": 8, "ngroups": 1,
                    "chunk_size": 16}},
}
TRAFFIC = {"kind": "train", "seq_len": 64, "global_batch": 2, "mesh": None,
           "min_window_steps": 4, "trace_seconds": 0.0,
           "min_trace_steps": 4}
# set from CPU readings at this size (8 seeds each): the program read at most
# loss 7.0e-5, grad 1.5e-2, update 3.2e-2; the fp8 control at least 2.2e-4,
# 2.7e-2, 4.9e-2
LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.022, "update_gap": 0.045}


def smoke_cell(config: str, dtype: str = "bfloat16", mesh=None,
               batch: int = 2) -> cells.Cell:
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    conf.update(copy.deepcopy(SMOKE[config]))
    conf["param_dtype"] = conf["compute_dtype"] = dtype
    traffic = dict(TRAFFIC, mesh=mesh, global_batch=batch)
    return cells.Cell(
        name=f"{config}.smoke", chips=1 if mesh is None else 4, config=conf,
        model=cells.load_module(BENCH / "configs" / f"{config}.py"),
        traffic=traffic, limits=dict(LIMITS), end_to_end=[], per_layer=[])


def no_remat(cell: cells.Cell):
    """The program's config for a smoke cell, with remat off."""
    from chipbench import train
    return replace(train.program_config(cell), remat="none")
