"""Mamba-2's SSD trained at its published chunk of 256, on the CPU at smoke
widths (d_model 64, 16 heads of 8, d_state 16, 2 layers):

* three training steps of the program (``Trainer.fit``) against the plain
  float32 reference of the benchmark (``chipbench/configs/mamba2-1.3b.py``
  through ``chipbench/plain.py``, which imports nothing of the program),
  read as the benchmark reads them (``chipbench/check.py``);
* every gradient leaf of ``ssd_apply`` finite where the decay's exponent
  overflows above the chunk's diagonal;
* one train step on a (data=2, model=2) mesh of four CPU devices against
  the same step on one device, and on meshes whose ``model`` axis cannot
  shard the heads (the in-projections' plain path);
* the in-projections lowered without a mesh: the three plain products.

At chunk 16 over 64 tokens the decay above the diagonal stays finite, so
only the chunk-256 cases see a decay masked after its exponential."""

import os
import re
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, plain, train  # noqa: E402
from chipbench.tests.smoke import smoke_cell  # noqa: E402

SEEDS = [3, 7, 2147483659]
# Largest CPU readings over the three seeds: chunk 16 / 64 tokens loss 4.4e-5,
# gradient 5.2e-3; chunk 256 / 512 tokens loss 2.2e-5, gradient 5.3e-3.
LOSS_TOL = 1.5e-4
GRAD_TOL = 0.016


@pytest.fixture(scope="module")
def trainers():
    """One Trainer per (chunk, tokens), compiled once and re-seeded."""
    return {}


def _cell(chunk: int, seq: int):
    cell = smoke_cell("mamba2-1.3b")
    cell.config["ssm_cfg"]["chunk_size"] = chunk
    return replace(cell, traffic=dict(cell.traffic, seq_len=seq))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("chunk, seq", [(16, 64), (256, 512)])
def test_program_matches_the_reference(trainers, chunk, seq, seed):
    cell = _cell(chunk, seq)
    devices = jax.devices()[:1]
    if (chunk, seq) not in trainers:
        tr = train.build(cell, seed, devices)
        tr.step_jit = train.StepRecorder(tr.step_jit)
        trainers[chunk, seq] = tr
    tr = trainers[chunk, seq]
    tr.seed = seed
    tr.step_jit.calls.clear()
    prog = train.program_readings(tr, tr.step_jit)
    model = cell.model.model(cell.config)
    batches = [plain.tokens(model.vocab, 2, seq, s, seed)
               for s in range(train.CHECK_STEPS)]
    ref = plain.train(model, seed, batches, plain.Arith(), devices)
    got = check.readings(prog, ref)
    assert got["loss_gap"] <= LOSS_TOL, got
    assert got["grad_gap"] <= GRAD_TOL, got


def test_ssd_gradients_are_finite_at_chunk_256():
    """dt = softplus(x W_dt + dt_bias) with the initial dt_bias 0 and A = -1:
    above the diagonal the decay's exponent sums about 0.7 a position, over
    255 positions far past float32's exp range."""
    from repro.configs import smoke_config
    from repro.models.layers import materialize
    from repro.models.ssm import ssd_apply, ssm_specs
    base = smoke_config("mamba2-1.3b")
    cfg = replace(base, ssm=replace(base.ssm, chunk=256))
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     materialize(ssm_specs(cfg), jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, cfg.d_model))
    loss, grads = jax.value_and_grad(
        lambda p, x: jnp.sum(ssd_apply(p, x, cfg) ** 2), (0, 1))(p, x)
    assert np.isfinite(float(loss))
    bad = [jax.tree_util.keystr(k) for k, g in
           jax.tree_util.tree_flatten_with_path(grads)[0]
           if not np.all(np.isfinite(np.asarray(g)))]
    assert not bad, bad


MESH_SSD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from dataclasses import replace
import jax, numpy as np
from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import Trainer

base = smoke_config("mamba2-1.3b")
cfg = replace(base, ssm=replace(base.ssm, chunk=256),
              param_dtype="float32", compute_dtype="float32")
assert cfg.ssm.chunk == 256
shape = ShapeConfig("mesh", seq_len=512, global_batch=2, kind="train")
got = {}
for name, mesh in [("one", None),
                   ("mesh", make_mesh((2, 2), ("data", "model")))]:
    tr = Trainer(cfg, shape, mesh, seed=2147483659)
    loss = tr.fit(1)[0]["loss"]
    params, opt = tr._last_state
    if mesh is not None:
        spec = tr.p_sh["scan"]["0"]["mixer"]["A_log"].spec
        assert "model" in str(spec), spec
    # AdamW's first moment: (1 - b1) x the clipped first gradient
    got[name] = (loss, jax.tree.map(np.asarray, opt["m"]),
                 [p.dtype for p in jax.tree.leaves(params)])
(l1, g1, dtypes), (l4, g4, _) = got["one"], got["mesh"]
# CPU readings (seed above): loss gap 8.6e-8; float32 leaves at most 2.1e-5 of
# the leaf's largest entry, the matrices stored in bf16 (whose gradients
# are bf16) at most 7.3e-4
assert np.isfinite(l1) and abs(l4 - l1) <= 1e-5 * abs(l1), (l1, l4)
for (k, a), b, dt in zip(jax.tree_util.tree_flatten_with_path(g4)[0],
                         jax.tree.leaves(g1), dtypes, strict=True):
    assert np.all(np.isfinite(a)), jax.tree_util.keystr(k)
    tol = 1e-4 if dt == np.float32 else 3e-3
    np.testing.assert_allclose(a, b, atol=tol * float(np.max(np.abs(b))),
                               err_msg=jax.tree_util.keystr(k))
print("MESH_SSD_OK")
"""


def test_train_step_on_a_mesh_matches_one_device():
    """A (data=2, model=2) mesh of four CPU devices (a fresh process: the
    tests see one): the SSD leaves sharded over ``model``, the batch over
    ``data``; the loss and every leaf's first moment match one device."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MESH_SSD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_SSD_OK" in proc.stdout


PLAIN_IN_PROJ = """
import os
import re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from dataclasses import replace
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_mesh
from repro.launch.train import Trainer
from repro.models.layers import materialize
from repro.models.ssm import _in_proj, ssm_specs

base = smoke_config("mamba2-1.3b")
cfg = replace(base, ssm=replace(base.ssm, chunk=256),
              param_dtype="float32", compute_dtype="float32")
p = materialize(ssm_specs(cfg), jax.random.PRNGKey(0))
x = jnp.ones((4, 512, cfg.d_model), jnp.bfloat16)


def in_proj_jaxpr(mesh):
    loss = lambda p, x: sum(o.astype(jnp.float32).sum()
                            for o in _in_proj(p, x))
    with use_mesh(mesh):
        return str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(p, x))


# model=2 divides the 16 heads: the map, whose backward psums x's gradient
# over model once (the weights' gradients over data, one each); no model
# axis, or model=3, which does not divide: the plain products
meshed = in_proj_jaxpr(make_mesh((2, 2), ("data", "model")))
assert "shard_map" in meshed, meshed
n_psum = len(re.findall(r"psum\\w*\\[\\s*axes=\\('model',\\)", meshed))
assert n_psum == 1, meshed
meshes = {"data4": make_mesh((4,), ("data",)),
          "model3": make_mesh((1, 3), ("data", "model"))}
for mesh in meshes.values():
    assert "shard_map" not in in_proj_jaxpr(mesh)

shape = ShapeConfig("mesh", seq_len=512, global_batch=4, kind="train")
got = {}
for name, mesh in [("one", None), *meshes.items()]:
    tr = Trainer(cfg, shape, mesh, seed=2147483659)
    loss = tr.fit(1)[0]["loss"]
    params, opt = tr._last_state
    got[name] = (loss, jax.tree.map(np.asarray, opt["m"]),
                 [p.dtype for p in jax.tree.leaves(params)])
l1, g1, dtypes = got["one"]
for name in meshes:
    l4, g4, _ = got[name]
    assert np.isfinite(l1) and abs(l4 - l1) <= 1e-5 * abs(l1), (name, l1, l4)
    for (k, a), b, dt in zip(jax.tree_util.tree_flatten_with_path(g4)[0],
                             jax.tree.leaves(g1), dtypes, strict=True):
        tol = 1e-4 if dt == np.float32 else 3e-3
        np.testing.assert_allclose(
            a, b, atol=tol * float(np.max(np.abs(b))),
            err_msg=name + jax.tree_util.keystr(k))
print("PLAIN_IN_PROJ_OK")
"""


def test_in_proj_takes_the_plain_path_where_model_cannot_shard_heads():
    """Four CPU devices (a fresh process): under a (data=2, model=2) mesh
    the in-projections run in one ``shard_map`` with one psum in their
    backward; under a mesh with no ``model`` axis, or one of 3 that does not
    divide the 16 heads, they are the plain products, and a train step
    matches one device as closely as on the (data=2, model=2) mesh."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", PLAIN_IN_PROJ], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PLAIN_IN_PROJ_OK" in proc.stdout


@pytest.mark.parametrize("mesh_shape", [None, (1, 1)])
def test_in_proj_lowers_to_three_products_on_one_device(mesh_shape):
    """Without a mesh, or on a mesh of one device, ``_in_proj`` lowers to
    the operations of ``x @ wz, x @ wx, x @ wdt``: three dot_generals."""
    from repro.configs import smoke_config
    from repro.distributed.sharding import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.models.layers import materialize
    from repro.models.ssm import _in_proj, ssm_specs
    cfg = smoke_config("mamba2-1.3b")
    p = materialize(ssm_specs(cfg), jax.random.PRNGKey(0))
    x = jnp.ones((2, 64, cfg.d_model), jnp.bfloat16)
    mesh = mesh_shape and make_mesh(mesh_shape, ("data", "model"))

    def ops(f):
        with use_mesh(mesh):
            text = jax.jit(f).lower(p, x).as_text()
        return re.findall(r"stablehlo\.\w+", text)

    got = ops(_in_proj)
    assert got == ops(lambda p, x: (x @ p["wz"], x @ p["wx"], x @ p["wdt"]))
    assert got.count("stablehlo.dot_general") == 3, got
