"""The plain float32 reference against the program at a smoke size, through
the harness's own readings: the program passes the smoke limits on every
seed tried, and the reference computed with fp8 products (the control)
fails at least one of them on every seed."""

import jax
import pytest

from chipbench import check, plain, train
from chipbench.tests.smoke import LIMITS, smoke_cell

SEEDS = [3, 2**31 + 11]


def _readings(cell, seed):
    t = cell.traffic
    model = cell.model.model(cell.config)
    devices = jax.devices()[:1]
    tr = train.build(cell, seed, devices)
    rec = train.StepRecorder(tr.step_jit)
    tr.step_jit = rec
    prog = train.program_readings(tr, rec)
    batches = [plain.tokens(model.vocab, t["global_batch"], t["seq_len"], s,
                            seed) for s in range(train.CHECK_STEPS)]
    ref = plain.train(model, seed, batches, plain.Arith(), devices)
    ctl = plain.train(model, seed, batches, plain.Arith(fp8=True), devices)
    return check.readings(prog, ref), check.readings(ctl, ref)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", ["minicpm3-4b", "mamba2-1.3b"])
def test_program_passes_and_control_fails(config, seed):
    prog, ctl = _readings(smoke_cell(config), seed)
    assert all(prog[k] <= LIMITS[k] for k in LIMITS), prog
    assert any(ctl[k] > LIMITS[k] for k in LIMITS), ctl


def test_tokens_match_the_program_stream():
    from repro.data.pipeline import make_tokens
    cell = smoke_cell("mamba2-1.3b")
    from chipbench.train import program_config
    cfg = program_config(cell)
    for step, seed in [(0, 5), (2, 2**31 + 7)]:
        assert (plain.tokens(cfg.vocab, 2, 64, step, seed)
                == make_tokens(cfg, 2, 64, step, seed)).all()


def test_init_matches_the_program_weights():
    from repro.models.transformer import init_params
    cell = smoke_cell("minicpm3-4b")
    cfg = train.program_config(cell)
    prog = jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(9))
    ref = plain.init_params(cell.model.model(cell.config).specs, 9)
    flat_p = jax.tree_util.tree_flatten_with_path(prog)[0]
    flat_r = jax.tree_util.tree_flatten_with_path(ref)[0]
    assert [jax.tree_util.keystr(k) for k, _ in flat_p] == \
        [jax.tree_util.keystr(k) for k, _ in flat_r]
    assert all(bool((a == b).all()) for (_, a), (_, b) in
               zip(flat_p, flat_r, strict=True))
