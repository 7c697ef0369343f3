"""Compile-only guards for the TPU: the flash kernel at real widths, the
whole gemma3-1b train step, the benchmark's 6-layer MiniCPM3 train step,
and the MiniCPM3 smoke step with and without its named scopes, compiled
for one chip of a described ``v5e:2x2`` topology, and the MiniCPM3 step
and the benchmark's whole Mamba-2 step for all four as a mesh (no chip
attached).  Interpret-mode tests cannot see what this catches: block
tilings the chip refuses, operations Mosaic cannot lower, programs that do
not fit the chip's memory.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every worker of a
parallel run imports this file.  Keep these tests in this one file.
"""

import contextlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels import ops

GEMMA = get_config("gemma3-1b")
MAMBA = get_config("mamba2-1.3b")
# the benchmark cell's depth: 6 of MiniCPM3-4B's 62 layers at full width
MINICPM = replace(get_config("minicpm3-4b"), n_layers=6)
BATCH, SEQ = 2, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("window", [None, GEMMA.window])
def test_flash_attention_compiles(one_chip, window):
    H, Kv, hd = GEMMA.n_heads, GEMMA.n_kv_heads, GEMMA.head_dim_
    q = _sds(one_chip, (BATCH, SEQ, H, hd), jnp.bfloat16)
    kv = _sds(one_chip, (BATCH, SEQ, Kv, hd), jnp.bfloat16)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, True, window, 128, 128, False)
        return o.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_mla_flash_attention_compiles(one_chip):
    """The kernel at MLA's widths (40 heads, qk 96, v 64; 1 x 2048) with the
    blocks ``mla_attention`` passes, forward and backward."""
    from repro.models.attention import MLA_FLASH_BLOCK_K, MLA_FLASH_BLOCK_Q
    m, H = MINICPM.mla, MINICPM.n_heads
    qk = _sds(one_chip, (1, 2048, H, m.qk_head_dim), jnp.bfloat16)
    v = _sds(one_chip, (1, 2048, H, m.v_head_dim), jnp.bfloat16)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, True, None, MLA_FLASH_BLOCK_Q,
                                MLA_FLASH_BLOCK_K, False)
        return o.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)


def _train_step(one_chip, cfg, shape, mesh=None):
    """The Trainer's jitted step compiled for the chip, or for ``mesh`` (the
    step's own shardings place each argument), and the bytes of the
    parameters and optimizer state it takes."""
    from repro.data.pipeline import input_specs
    from repro.distributed.sharding import use_mesh
    from repro.launch.train import Trainer
    from repro.models.transformer import abstract_params

    def put(tree):
        where = one_chip if mesh is None else None
        return jax.tree.map(lambda s: _sds(where, s.shape, s.dtype), tree)

    tr = Trainer(cfg, shape, mesh)
    aparams = put(abstract_params(cfg))
    aopt = put(jax.eval_shape(tr.opt.init, aparams))
    with use_mesh(mesh):
        compiled = tr.step_jit.lower(
            aparams, aopt, put(input_specs(cfg, shape)),
            put(jax.ShapeDtypeStruct((), jnp.int32))).compile()
    state = sum(math.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves((aparams, aopt)))
    return compiled, state


@pytest.mark.parametrize("use_flash", [False, True])
def test_gemma3_train_step_compiles(one_chip, monkeypatch, use_flash):
    """The Trainer's own jitted step at full width, batch 2 x 1024: it must
    fit one chip (the compiler refuses it otherwise) and update the
    parameters and moments in place."""
    # the kernels pick interpret mode from the process's backend (CPU here)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = replace(GEMMA, use_flash=use_flash)
    compiled, state = _train_step(
        one_chip, cfg, ShapeConfig("chip_compile", SEQ, BATCH, "train"))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state     # params + moments donated
    assert ("tpu_custom_call" in compiled.as_text()) == use_flash


def test_minicpm3_train_step_compiles(one_chip, monkeypatch):
    """The benchmark cell's step, 6 MiniCPM3 layers at 1 x 2048: every
    Pallas call is MLA's flash kernel, under ``mla/flash``; no buffer holds
    the [..., 40, 2048, 2048] scores; the temporaries stay under 4 GiB
    (8.56 GiB with materialised scores)."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    compiled, _ = _train_step(one_chip, MINICPM,
                              ShapeConfig("chip_compile", 2048, 1, "train"))
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all("/mla/flash/" in line for line in calls)
    assert not re.search(r"\[(?:\d+,)*40,2048,2048\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


def test_minicpm3_mesh_train_step_compiles(topo, monkeypatch):
    """The same 6 layers at 2 x 2048 on a (data=2, model=2) mesh of the
    described chips.  XLA cannot partition a Mosaic call, so MLA's kernel
    runs inside a ``shard_map``: each chip takes one sequence and 20 of the
    40 heads, and no chip holds the whole batch or every head."""
    from jax.sharding import Mesh
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    compiled, _ = _train_step(None, MINICPM,
                              ShapeConfig("chip_compile", 2048, 2, "train"),
                              mesh)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all("/mla/flash/" in line for line in calls)
    # the kernel's operands are (batch x heads, S, 96): one sequence, 20 heads
    assert all("bf16[20,2048,96]" in line for line in calls)



def test_mamba2_mesh_train_step_compiles(topo):
    """The benchmark's Mamba-2 cell: all 48 layers at 2 x 4096 on a
    (data=2, model=2) mesh of the described chips, lowered from plain
    shapes outside ``use_mesh``, as the benchmark lowers the step to read
    its memory.  The step enters its own mesh, so this is the sharded
    program (3.43 GiB of arguments and 9.30 GiB of temporaries a chip);
    without the mesh's activation constraints it needs 19.04 GiB and the
    compiler refuses it.

    Each layer's backward all-reduces over ``model`` one sum of the three
    in-projections' input gradients (one ``bf16[1,4096,2048]`` operand,
    three when the partitioner reduces each partial), and its forward the
    one output of ``y @ wo``."""
    from jax.sharding import Mesh
    from repro.data.pipeline import input_specs
    from repro.launch.train import Trainer
    from repro.models.transformer import abstract_params
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    shape = ShapeConfig("chip_compile", 4096, 2, "train")
    tr = Trainer(MAMBA, shape, mesh)
    aparams = abstract_params(MAMBA)
    compiled = tr.step_jit.lower(
        aparams, jax.eval_shape(tr.opt.init, aparams),
        input_specs(MAMBA, shape),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13 * 2**30
    width = {"forward": 0, "backward": 0}
    for line in compiled.as_text().splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\(.*"
                      r'op_name="jit\(step_fn\)/([^"]*)"', line)
        if m and "/mixer/ssd/" in m.group(2):
            side = ("backward" if m.group(2).startswith("transpose(jvp())")
                    else "forward")
            width[side] += m.group(1).count("bf16[1,4096,2048]")
    assert width == {"forward": 1, "backward": 1}, width

# debug information only: op_name and source lines, and the tables of files,
# functions and stack frames they point into
_METADATA = re.compile(r',?\s*metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _without_debug_info(hlo: str) -> str:
    """``hlo`` without its debug information, and with each instruction and
    computation renamed by its first appearance: XLA names a custom call
    after its innermost scope (``%flash.31``, else ``%closed_call.31``)."""
    text = "\n\n".join(block for block in _METADATA.sub("", hlo).split("\n\n")
                        if block.split("\n", 1)[0] not in _DEBUG_TABLES)
    names: dict[str, str] = {}
    return re.sub(r"%[\w.-]+",
                  lambda m: names.setdefault(m.group(), f"%v{len(names)}"),
                  text)


def test_named_scopes_add_no_operations(one_chip, monkeypatch):
    """The MiniCPM3 step (smoke widths at S = 256, so MLA runs the Mosaic
    flash kernel, as in the cell), compiled for the chip with the
    model's named scopes and with ``jax.named_scope`` a no-op: the optimized
    HLO is the same once debug information is stripped."""
    from repro.configs import smoke_config

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = smoke_config("minicpm3-4b")
    shape = ShapeConfig("chip_compile", 256, 2, "train")

    def optimized_hlo():
        return _train_step(one_chip, cfg, shape)[0].as_text()

    scoped = optimized_hlo()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = optimized_hlo()
    assert re.search(r'op_name="[^"]*/mla/', scoped)
    assert not re.search(r'op_name="[^"]*/mla/', plain)
    assert _without_debug_info(scoped) == _without_debug_info(plain)
