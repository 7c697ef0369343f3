"""Compile-only guards for the TPU: every Pallas kernel at real widths and
the whole gemma3-1b train step, compiled for one chip of a described
``v5e:2x2`` topology (no chip attached).  Interpret-mode tests cannot see
what this catches: block tilings the chip refuses, operations Mosaic cannot
lower, programs that do not fit the chip's memory.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every worker of a
parallel run imports this file.  Keep these tests in this one file.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from dataclasses import replace
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels import ops

GEMMA = get_config("gemma3-1b")
MAMBA = get_config("mamba2-1.3b")
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


def test_fused_adam_compiles(one_chip):
    shp = (GEMMA.d_model, GEMMA.d_ff)
    bf = _sds(one_chip, shp, jnp.bfloat16)
    f32 = _sds(one_chip, shp, jnp.float32)
    _compile(lambda p, g, m, v, c: ops.fused_adam(
        p, g, m, v, c, lr=1e-3, weight_decay=0.01, interpret=False),
        bf, bf, f32, f32, _sds(one_chip, (), jnp.int32))


def test_rmsnorm_compiles(one_chip):
    _compile(lambda x, s: ops.rmsnorm(x, s, interpret=False),
             _sds(one_chip, (BATCH * SEQ, GEMMA.d_model), jnp.bfloat16),
             _sds(one_chip, (GEMMA.d_model,), jnp.float32))


def test_ssd_chunk_compiles(one_chip):
    s = MAMBA.ssm
    BH, Q = BATCH * MAMBA.ssm_heads, s.chunk
    nc = SEQ // Q
    _compile(lambda *a: ops.ssd_chunk(*a, interpret=False),
             _sds(one_chip, (BH, nc, Q, s.headdim), jnp.bfloat16),
             _sds(one_chip, (BH, nc, Q), jnp.float32),
             _sds(one_chip, (BH, nc, Q, s.d_state), jnp.bfloat16),
             _sds(one_chip, (BH, nc, Q, s.d_state), jnp.bfloat16),
             _sds(one_chip, (BH,), jnp.float32))


@pytest.mark.parametrize("use_flash", [False, True])
def test_gemma3_train_step_compiles(one_chip, monkeypatch, use_flash):
    """The Trainer's own jitted step at full width, batch 2 x 1024: it must
    fit one chip (the compiler refuses it otherwise) and update the
    parameters and moments in place."""
    from repro.data.pipeline import input_specs
    from repro.launch.train import Trainer
    from repro.models.transformer import abstract_params

    # the kernels pick interpret mode from the process's backend (CPU here)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = replace(GEMMA, use_flash=use_flash)
    shape = ShapeConfig("chip_compile", SEQ, BATCH, "train")
    tr = Trainer(cfg, shape)

    def put(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    aparams = put(abstract_params(cfg))
    aopt = put(jax.eval_shape(tr.opt.init, aparams))
    compiled = tr.step_jit.lower(aparams, aopt, put(input_specs(cfg, shape)),
                                 _sds(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    state = sum(math.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves((aparams, aopt)))
    assert mem.alias_size_in_bytes >= state     # params + moments donated
    assert ("tpu_custom_call" in compiled.as_text()) == use_flash
