"""Per-architecture smoke tests: reduced config, one forward + one train
step + one decode step on CPU; asserts output shapes + no NaNs (assignment
deliverable f).  The RMSNorm and the chunked SSD the models train with,
each against an oracle that shares none of its code path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.data.pipeline import make_batch
from repro.configs.base import ShapeConfig
from repro.models import decode_step, init_cache, init_params, logits_fn
from repro.optim.optimizers import make_optimizer
from repro.training.train_step import make_serve_step, make_train_step

SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    arch = request.param
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return arch, cfg, params


def _finite(x) -> bool:
    return bool(np.all(np.isfinite(np.asarray(x, np.float32))))


def test_forward_shapes_and_finite(arch_setup):
    arch, cfg, params = arch_setup
    batch = make_batch(cfg, SMOKE_SHAPE, 0)
    logits, aux = jax.jit(lambda p, x: logits_fn(p, cfg, x))(
        params, batch["inputs"])
    assert logits.shape == (2, 32, cfg.vocab)
    assert _finite(logits)
    assert _finite(aux)


def test_train_step_updates_and_finite(arch_setup):
    arch, cfg, params = arch_setup
    opt = make_optimizer("adamw", 1e-3, state_dtype=cfg.state_dtype)
    step = jax.jit(make_train_step(cfg, opt))
    opt_state = opt.init(params)
    batch = make_batch(cfg, SMOKE_SHAPE, 0)
    new_params, _, metrics = step(params, opt_state, batch, jnp.int32(0))
    assert _finite(metrics["loss"]) and metrics["loss"] > 0
    # params actually moved
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), params, new_params)
    assert max(jax.tree.leaves(diffs)) > 0


def test_decode_step_and_cache(arch_setup):
    arch, cfg, params = arch_setup
    B, T = 2, 16
    cache = init_cache(cfg, B, T)
    serve = make_serve_step(cfg)
    if cfg.input_mode == "tokens":
        inp = jnp.zeros((B, 1), jnp.int32)
    else:
        inp = jnp.zeros((B, 1, cfg.d_model), jnp.bfloat16)
    nxt, cache = serve(params, cache, inp, jnp.int32(0))
    assert nxt.shape == (B,)
    nxt2, cache = serve(params, cache, inp, jnp.int32(1))
    assert _finite(nxt2)


def test_decode_matches_forward_logits(arch_setup):
    """Greedy decode over a short prompt == argmax of teacher-forced fwd."""
    arch, cfg, params = arch_setup
    if cfg.input_mode != "tokens":
        pytest.skip("embedding-input arch: positions fed by frontend stub")
    B, S = 1, 8
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (B, S)), jnp.int32)
    logits, _ = logits_fn(params, cfg, toks)
    cache = init_cache(cfg, B, S)
    outs = []
    for t in range(S):
        lg, cache = decode_step(params, cache, cfg, toks[:, t:t + 1],
                                jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    # bf16 params + chunked-vs-recurrent SSD orderings: ~0.07 worst-case
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(logits, np.float32),
                               atol=1.5e-1, rtol=1e-1)


# largest readings on the CPU: 2.5e-7 (float32) and 1.0e-2 (bfloat16, of
# the 1.18e-2 its three roundings allow)
RMSNORM_RTOL = {jnp.float32: 1e-6, jnp.bfloat16: 2.0 ** -6}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (2, 33, 256), (1, 7, 5, 64)])
def test_rmsnorm_matches_numpy(shape, dtype):
    """The models' RMSNorm (gain 1 + w) against the formula in float64.
    bfloat16 rounds the normalised input, the gain and their product, each
    within 2**-8 of its value."""
    from repro.models.layers import rmsnorm
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]) * 0.1, jnp.float32)
    y = jax.jit(rmsnorm)(x, w)
    x64 = np.asarray(x, np.float64)
    want = (x64 / np.sqrt(np.mean(x64 ** 2, -1, keepdims=True) + 1e-6)
            * (1 + np.asarray(w, np.float64)))
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float64), want,
                               rtol=RMSNORM_RTOL[dtype], atol=0)


def _ssd_by_recurrence(p, x, cfg):
    """``ssd_decode_step`` token by token under ``lax.scan``: the sequential
    recurrence of the state, with no chunks."""
    from repro.models.ssm import ssd_decode_step
    B = x.shape[0]
    s = cfg.ssm
    conv = jnp.zeros((B, s.conv_width - 1,
                      cfg.d_inner + 2 * s.n_groups * s.d_state), jnp.float32)
    state = jnp.zeros((B, cfg.ssm_heads, s.headdim, s.d_state), jnp.float32)

    def step(carry, xt):
        y, conv, state = ssd_decode_step(p, xt[:, None], *carry, cfg)
        return (conv, state), y[:, 0]

    _, ys = jax.lax.scan(step, (conv, state), x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2)


# of the largest output; the largest reading on the CPU is 1.25e-5, at
# chunk 256 with the shifted dt_bias
SSD_RTOL = 1e-4


@pytest.mark.parametrize("chunk,d_state,n_groups,S,dt_shift", [
    (8, 16, 1, 32, 0.0), (32, 128, 1, 64, 0.0), (16, 32, 2, 64, 0.0),
    (256, 16, 1, 512, 0.0), (256, 128, 1, 512, 1.0)])
def test_ssd_apply_matches_recurrent_decode(chunk, d_state, n_groups, S,
                                            dt_shift):
    """The chunked SSD that training runs (``ssd_apply``: decay inside each
    chunk, states carried across chunks by a scan) against the token-by-
    token recurrence, in float32.  At chunk 256 the decay's exponent above
    the diagonal leaves float32's ``exp`` range; with ``dt_bias`` shifted
    by one it goes further, and the gradient must stay finite (the mask
    comes before the exponential)."""
    from dataclasses import replace
    from repro.models.layers import materialize
    from repro.models.ssm import ssd_apply, ssm_specs
    base = smoke_config("mamba2-1.3b")
    cfg = replace(base, ssm=replace(base.ssm, chunk=chunk, d_state=d_state,
                                    n_groups=n_groups))
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     materialize(ssm_specs(cfg), jax.random.PRNGKey(1)))
    p["dt_bias"] = p["dt_bias"] + dt_shift
    B = 2
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    got = jax.jit(lambda p, x: ssd_apply(p, x, cfg))(p, x)
    want = jax.jit(lambda p, x: _ssd_by_recurrence(p, x, cfg))(p, x)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= SSD_RTOL * float(jnp.max(jnp.abs(want))), err
    if dt_shift:
        # A = -exp(A_log) = -1, so -A * dt summed over a chunk is the
        # largest exponent: past log(float32 max) ~ 88.7
        dt = jax.nn.softplus(x @ p["wdt"] + p["dt_bias"])
        assert float(dt.reshape(B, -1, chunk, dt.shape[-1]).sum(2).max()) > 100
        grads = jax.grad(lambda p, x: jnp.sum(ssd_apply(p, x, cfg) ** 2),
                         argnums=(0, 1))(p, x)
        assert all(np.all(np.isfinite(np.asarray(g)))
                   for g in jax.tree.leaves(grads))


def test_full_config_param_counts():
    expected = {
        "nemotron-4-340b": (320e9, 360e9),
        "gemma3-1b": (0.9e9, 1.2e9),
        "phi3-medium-14b": (13e9, 16e9),
        "minicpm3-4b": (3.8e9, 4.8e9),
        "mamba2-1.3b": (1.2e9, 1.6e9),
        "olmoe-1b-7b": (6.3e9, 7.5e9),
        "jamba-1.5-large-398b": (370e9, 420e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, (arch, n)


def test_moe_active_params():
    cfg = get_config("olmoe-1b-7b")
    assert cfg.active_param_count() < 0.25 * cfg.param_count()


# examples/serve_lm.py moved to the analytic serving axis — its end-to-end
# test (Pareto front + CSV artifact) lives in tests/test_serving.py
