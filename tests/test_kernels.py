"""The Pallas flash-attention kernel vs its pure-jnp oracle and the models'
materialised attention: shape/dtype sweeps (interpret mode)."""

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
@pytest.mark.parametrize("H,Kv,hv", [
    pytest.param(4, 4, 64, id="4-4"), pytest.param(4, 2, 64, id="4-2"),
    pytest.param(8, 1, 64, id="8-1"), pytest.param(4, 4, 32, id="4-4-v32"),
    pytest.param(4, 2, 32, id="4-2-v32")])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_sweep(S, T, H, Kv, hv, dtype):
    """q/k head dim 64; v's 64 or its own 32."""
    rng = np.random.default_rng(0)
    B, hd = 2, 64
    q = rand(rng, (B, S, H, hd), dtype)
    k = rand(rng, (B, T, Kv, hd), dtype)
    v = rand(rng, (B, T, Kv, hv), dtype)
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


def _materialised_attention(q, k, v, window):
    """The models' materialised path in q's dtype: scores from the products'
    own dtype, softmax in f32, probabilities cast back before the context."""
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    s = jnp.einsum("bskgd,btkd->bkgst", q.reshape(B, S, Kv, H // Kv, hd),
                   k) / np.sqrt(hd)
    qi, ki = np.arange(S)[:, None], np.arange(S)[None]
    mask = (ki <= qi) & (qi - ki < (window or S))
    p = jax.nn.softmax(jnp.where(mask, s, ref.NEG_INF).astype(jnp.float32),
                       -1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(B, S, H, -1)


@pytest.mark.parametrize("H,Kv,hd,hv,window,dtype", [
    pytest.param(4, 4, 32, 32, None, jnp.float32, id="4-4"),
    pytest.param(4, 1, 32, 32, None, jnp.float32, id="4-1"),
    pytest.param(4, 4, 96, 64, None, jnp.float32, id="4-4-qk96-v64"),
    pytest.param(4, 2, 48, 32, None, jnp.float32, id="4-2-qk48-v32"),
    pytest.param(4, 1, 32, 32, None, jnp.bfloat16, id="4-1-bf16"),
    pytest.param(4, 4, 96, 64, None, jnp.bfloat16, id="4-4-qk96-v64-bf16"),
    pytest.param(4, 2, 48, 32, None, jnp.bfloat16, id="4-2-qk48-v32-bf16"),
    pytest.param(4, 4, 96, 64, 48, jnp.bfloat16, id="4-4-qk96-v64-w48-bf16"),
    pytest.param(4, 2, 32, 32, 40, jnp.bfloat16, id="4-2-w40-bf16")])
def test_flash_grads_match_ref(H, Kv, hd, hv, window, dtype):
    """hd != hv: MLA's shape (qk 96, v 64) and a grouped one.  In bf16 (the
    MXU fed bf16, p and ds rounded before their products) each gradient's
    error against the f32 reference stays within 1.5x the largest of the
    bf16 materialised path's own."""
    rng = np.random.default_rng(2)
    B, S = 1, 128
    q = rand(rng, (B, S, H, hd), dtype)
    k = rand(rng, (B, S, Kv, hd), dtype)
    v = rand(rng, (B, S, Kv, hv), dtype)

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.tanh(
            attn(q, k, v).astype(jnp.float32)))

    g = jax.grad(loss(lambda q, k, v: ops.flash_attention(
        q, k, v, True, window, 64, 64)), argnums=(0, 1, 2))(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    gr = jax.grad(loss(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=True, window=window)), argnums=(0, 1, 2))(*f32)
    if dtype == jnp.float32:
        for a, b in zip(g, gr, strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        return

    def errors(grads):
        return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) /
                      jnp.max(jnp.abs(b)))
                for a, b in zip(grads, gr, strict=True)]

    gm = jax.grad(loss(lambda q, k, v: _materialised_attention(
        q, k, v, window)), argnums=(0, 1, 2))(q, k, v)
    assert all(a.dtype == dtype for a in g)
    tol = 1.5 * max(errors(gm))
    assert max(errors(g)) <= tol, (errors(g), errors(gm))


def test_flash_noncausal():
    rng = np.random.default_rng(3)
    B, S, H, hd = 1, 64, 2, 16
    q = rand(rng, (B, S, H, hd), jnp.float32)
    k = rand(rng, (B, S, H, hd), jnp.float32)
    v = rand(rng, (B, S, H, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, False, None, 32, 32)
    o_ref = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


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


def _mla_inputs(S):
    """MiniCPM3's MLA at smoke widths (qk 16 = nope 8 + rope 8, v 8) in f32,
    with the materialised path (use_flash off)."""
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.models.attention import mla_specs
    from repro.models.layers import materialize
    cfg = replace(smoke_config("minicpm3-4b"), use_flash=False)
    p = materialize(mla_specs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    return cfg, p, x, pos


@pytest.mark.parametrize("S", [128, 256])
def test_mla_flash_path_matches_materialised(S):
    """MLA through the kernel ≡ MLA with materialised scores: the output and
    the gradient of every MLA leaf and of the input."""
    from dataclasses import replace
    from repro.models.attention import mla_attention
    cfg, p, x, pos = _mla_inputs(S)
    cfgf = replace(cfg, use_flash=True)

    def loss(c):
        return lambda p, x: jnp.sum(jnp.tanh(mla_attention(p, x, c, pos)))

    np.testing.assert_allclose(np.asarray(mla_attention(p, x, cfgf, pos)),
                               np.asarray(mla_attention(p, x, cfg, pos)),
                               atol=3e-5)
    g = jax.grad(loss(cfgf), argnums=(0, 1))(p, x)
    gr = jax.grad(loss(cfg), argnums=(0, 1))(p, x)
    assert set(g[0]) == set(gr[0]) == set(p)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("S", [64, 128])
def test_mla_flash_path_only_at_aligned_seq(S):
    """With use_flash, MLA calls the kernel when S is a multiple of 128 and
    otherwise keeps the materialised path, unchanged."""
    from dataclasses import replace
    from repro.models.attention import mla_attention
    cfg, p, x, pos = _mla_inputs(S)
    cfgf = replace(cfg, use_flash=True)
    jaxpr = str(jax.make_jaxpr(
        lambda p, x: mla_attention(p, x, cfgf, pos))(p, x))
    assert ("pallas_call" in jaxpr) == (S % 128 == 0)
    if S % 128:
        np.testing.assert_array_equal(
            np.asarray(mla_attention(p, x, cfgf, pos)),
            np.asarray(mla_attention(p, x, cfg, pos)))


MESH_FLASH = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from dataclasses import replace
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_mesh
from repro.models import attention as A
from repro.models.layers import materialize

mesh = make_mesh((2, 2), ("data", "model"))
for name, h, kv, specs, attn in [
        ("minicpm3-4b", 4, 4, A.mla_specs, A.mla_attention),
        ("phi3-medium-14b", 4, 4, A.attn_specs, A.gqa_attention),
        ("phi3-medium-14b", 6, 3, A.attn_specs, A.gqa_attention)]:
    cfg = replace(smoke_config(name), n_heads=h, n_kv_heads=kv,
                  attn_chunked=False, use_flash=False)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     materialize(specs(cfg), jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))

    def grads(c):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(jnp.tanh(attn(p, x, c, pos))), (0, 1)))

    want = grads(cfg)(p, x)
    with use_mesh(mesh):
        f = grads(replace(cfg, use_flash=True))
        got = f(p, x)
        assert "sdy.manual_computation" in f.lower(p, x).as_text()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5 * float(jnp.max(jnp.abs(b))))
print("MESH_FLASH_OK")
"""


def test_flash_path_on_a_mesh_matches_materialised():
    """On a (data=2, model=2) mesh of four CPU devices (a fresh process:
    the tests see one), MLA and GQA with ``use_flash`` run the kernel inside
    a ``shard_map`` and match their materialised paths without a mesh:
    heads split over the model axis, or kept whole where it divides H = 6
    but not Kv = 3."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MESH_FLASH], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_FLASH_OK" in proc.stdout
