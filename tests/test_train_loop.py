"""Training-loop integration: convergence, checkpoint/restart, failure
recovery, optimizer math, chunked loss equivalence."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.store import (AsyncCheckpointer, available_steps,
                              latest_step, load_checkpoint, prune_checkpoints,
                              save_checkpoint)
from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch import spans
from repro.launch.train import Trainer
from repro.optim.optimizers import (adafactor, adamw, clip_by_global_norm,
                                    galore_adamw, global_norm, sgd_momentum,
                                    warmup_cosine)

SHAPE = ShapeConfig("t", seq_len=64, global_batch=4, kind="train")


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("gemma3-1b")


def test_loss_decreases(cfg, tmp_path_factory):
    tr = Trainer(cfg, SHAPE, lr=1e-2)
    logs = tr.fit(30)
    first = np.mean([l["loss"] for l in logs[:5]])
    last = np.mean([l["loss"] for l in logs[-5:]])
    assert last < first - 1e-3


def test_checkpoint_resume_exact(cfg, tmp_path):
    ck = str(tmp_path / "ck")
    tr1 = Trainer(cfg, SHAPE, lr=1e-3, ckpt_dir=ck, ckpt_every=5)
    tr1.fit(10)
    tr1.ckpt.close()
    p_full, o_full = tr1._last_state

    # fresh trainer resumes from step 10 checkpoint and continues to 12
    tr2 = Trainer(cfg, SHAPE, lr=1e-3, ckpt_dir=ck, ckpt_every=100)
    logs2 = tr2.fit(12)
    assert logs2[0]["step"] == 10

    # one-shot trainer that runs 12 steps without interruption
    tr3 = Trainer(cfg, SHAPE, lr=1e-3)
    logs3 = tr3.fit(12)
    assert abs(logs3[-1]["loss"] - logs2[-1]["loss"]) < 1e-4


def test_failure_injection_recovers(cfg, tmp_path):
    ck = str(tmp_path / "ck")
    tr = Trainer(cfg, SHAPE, lr=1e-3, ckpt_dir=ck, ckpt_every=4)
    logs = tr.fit(10, inject_failure_at=6)
    assert tr.failures == 1
    assert logs[-1]["step"] == 9
    # steps 4..6 re-run after restore from the step-4 checkpoint
    steps = [l["step"] for l in logs]
    assert steps.count(5) >= 1
    # the failed step's span closed; the failure path is recorded
    assert [s.name for s in spans.fits()[-1].spans if s.step == 6][:4] == [
        "fit.data", "fit.step", "fit.restore", "fit.recover"]


def test_straggler_watchdog(cfg):
    tr = Trainer(cfg, SHAPE, lr=1e-3, straggler_factor=0.0)
    tr.fit(8)
    assert tr.stragglers > 0            # every step flagged at factor 0


def test_repeated_failure_after_restore_raises(cfg, tmp_path, monkeypatch):
    """A step that fails again after the restore re-raises instead of
    restoring forever."""
    import repro.launch.train as train_mod

    def make_failing_step(*a, **k):
        def step(*args):
            raise RuntimeError("step always fails")
        return step

    monkeypatch.setattr(train_mod, "make_train_step", make_failing_step)
    tr = Trainer(cfg, SHAPE, ckpt_dir=str(tmp_path / "ck"))
    with pytest.raises(RuntimeError, match="always fails"):
        tr.fit(4)
    assert tr.failures == 2


def test_trainer_on_mesh_matches_no_mesh(cfg):
    """The mesh path (state built sharded under jit, pinned step shardings)
    trains like the single-device path."""
    from repro.launch.mesh import make_mesh
    plain = [m["loss"] for m in Trainer(cfg, SHAPE, lr=1e-3).fit(3)]
    tr = Trainer(cfg, SHAPE, make_mesh((1, 1), ("data", "model")), lr=1e-3)
    meshed = [m["loss"] for m in tr.fit(3)]
    np.testing.assert_allclose(meshed, plain, rtol=1e-5)
    params, opt_state = tr._last_state
    for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(tr.p_sh),
                        strict=True):
        assert leaf.sharding == sh



def test_mesh_step_lowers_alike_in_and_out_of_use_mesh(cfg):
    """The model's activation constraints read the active mesh while the
    step is traced.  The jitted step enters its own mesh, so lowering it
    from outside ``use_mesh`` (as the chip benchmark does to read the
    step's memory) gives the program ``fit`` runs, constraints and all."""
    from repro.data.pipeline import input_specs
    from repro.distributed.sharding import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import abstract_params
    mesh = make_mesh((1, 1), ("data", "model"))
    aparams = abstract_params(cfg)

    def lowered(tr):
        return tr.step_jit.lower(
            aparams, jax.eval_shape(tr.opt.init, aparams),
            input_specs(cfg, SHAPE),
            jax.ShapeDtypeStruct((), jnp.int32)).as_text()

    outside = lowered(Trainer(cfg, SHAPE, mesh))
    with use_mesh(mesh):
        inside = lowered(Trainer(cfg, SHAPE, mesh))
    assert "sharding_constraint" in inside
    assert outside == inside

def test_compile_cache_location(monkeypatch):
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/env")
    assert cc.enable_compile_cache() == "/set/by/env"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cc.enable_compile_cache() == str(cc.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(cc.CHECKOUT_CACHE)
        assert cc.CHECKOUT_CACHE.parent == \
            cc.Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# -- checkpoint store ----------------------------------------------------------


def test_ckpt_roundtrip_bf16(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32)}}
    save_checkpoint(str(tmp_path), 3, tree)
    out, manifest = load_checkpoint(str(tmp_path), tree)
    assert manifest["step"] == 3
    assert out["a"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))


def test_ckpt_atomic_and_prune(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), s, tree)
    assert latest_step(str(tmp_path)) == 4
    prune_checkpoints(str(tmp_path), keep=2)
    assert available_steps(str(tmp_path)) == [3, 4]
    # a stray tmp dir is never listed
    os.makedirs(tmp_path / ".tmp_9", exist_ok=True)
    assert latest_step(str(tmp_path)) == 4


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    for s in (1, 2):
        ck.save(s, {"w": jnp.full((8,), s, jnp.float32)})
    ck.close()
    out, m = load_checkpoint(str(tmp_path), {"w": jnp.zeros((8,))})
    assert m["step"] == 2 and float(out["w"][0]) == 2.0


def test_ckpt_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), {"w": jnp.zeros((5,))})


# -- optimizers ------------------------------------------------------------------


def test_adamw_matches_manual():
    p = {"w": jnp.ones((4,), jnp.float32)}
    g = {"w": jnp.full((4,), 0.5, jnp.float32)}
    opt = adamw(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0)
    st = opt.init(p)
    newp, st = opt.update(g, st, p, 0)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    step = (m / 0.1) / (np.sqrt(v / 0.01) + 1e-8)
    np.testing.assert_allclose(np.asarray(newp["w"]), 1.0 - 0.1 * step,
                               rtol=1e-6)


def test_adamw_state_dtype_bf16():
    p = {"w": jnp.ones((4,), jnp.float32)}
    opt = adamw(state_dtype="bfloat16")
    st = opt.init(p)
    assert st["m"]["w"].dtype == jnp.bfloat16


def test_sgd_momentum_two_steps():
    p = {"w": jnp.zeros((2,), jnp.float32)}
    g = {"w": jnp.ones((2,), jnp.float32)}
    opt = sgd_momentum(lr=1.0, momentum=0.5)
    st = opt.init(p)
    p1, st = opt.update(g, st, p, 0)
    p2, st = opt.update(g, st, p1, 1)
    np.testing.assert_allclose(np.asarray(p2["w"]), [-2.5, -2.5])


def test_adafactor_memory_factored():
    p = {"w": jnp.ones((32, 16), jnp.float32)}
    opt = adafactor(lr=1e-2)
    st = opt.init(p)
    assert st["f"]["w"]["r"].shape == (32,)
    assert st["f"]["w"]["c"].shape == (16,)
    g = {"w": jnp.ones((32, 16), jnp.float32)}
    newp, _ = opt.update(g, st, p, 0)
    assert float(jnp.max(jnp.abs(newp["w"] - p["w"]))) > 0


def test_galore_low_rank_states():
    p = {"w": jnp.ones((512, 256), jnp.float32)}
    opt = galore_adamw(lr=1e-3, rank=16)
    st = opt.init(p)
    assert st["s"]["w"]["m"].shape == (16, 256)      # compressed moments
    assert st["s"]["w"]["P"].shape == (512, 16)
    g = {"w": jnp.ones((512, 256), jnp.float32)}
    newp, st2 = opt.update(g, st, p, 0)
    assert float(jnp.max(jnp.abs(newp["w"] - p["w"]))) > 0
    # orthonormal projector
    PtP = np.asarray(st["s"]["w"]["P"]).T @ np.asarray(st["s"]["w"]["P"])
    np.testing.assert_allclose(PtP, np.eye(16), atol=1e-5)


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(float(norm), 10.0, rtol=1e-6)


def test_warmup_cosine_schedule():
    lr = warmup_cosine(1.0, warmup=10, total=100)
    assert float(lr(0)) < float(lr(9))
    np.testing.assert_allclose(float(lr(10)), 1.0, rtol=1e-3)
    assert float(lr(99)) < 0.2


# -- loss ------------------------------------------------------------------------


def test_chunked_loss_equals_unchunked(cfg):
    from repro.models import init_params
    from repro.training.loss import lm_loss
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)

    def run(chunk):
        def f(p):
            return lm_loss(p, cfg, inputs, labels, loss_chunk=chunk)[0]
        return jax.value_and_grad(f)(params)

    l0, g0 = run(None)
    l1, g1 = run(16)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1), strict=True):
        # grads are stored in bf16: equal to within one ulp
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-3, rtol=1e-2)


def test_masked_labels_ignored(cfg):
    from repro.models import init_params
    from repro.training.loss import lm_loss
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.integers(0, cfg.vocab, (1, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab, (1, 32)), jnp.int32)
    masked = labels.at[:, 16:].set(-1)
    _, m1 = lm_loss(params, cfg, inputs, labels)
    _, m2 = lm_loss(params, cfg, inputs, masked)
    assert float(m2["tokens"]) == 16.0
    assert float(m1["tokens"]) == 32.0


def test_grad_accum_equivalence(cfg):
    from repro.models import init_params
    from repro.optim.optimizers import sgd_momentum
    from repro.training.train_step import make_train_step
    from repro.data.pipeline import make_batch
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, SHAPE, 0)
    opt = sgd_momentum(lr=1e-2)

    outs = {}
    for ga in (1, 2):
        step = jax.jit(make_train_step(cfg, opt, grad_accum=ga))
        p2, _, m = step(jax.tree.map(jnp.copy, params), opt.init(params),
                        batch, jnp.int32(0))
        outs[ga] = p2
    for a, b in zip(jax.tree.leaves(outs[1]), jax.tree.leaves(outs[2]), strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-2)
