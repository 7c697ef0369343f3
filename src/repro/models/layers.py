"""Core layers: parameter specs, RMSNorm, RoPE, MLP variants.

Parameters are plain nested dicts of jnp arrays.  Every leaf is declared via
``PSpec`` (shape + logical sharding axes + dtype), so the same definition
yields random inits for real runs and ShapeDtypeStructs for the dry-run.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import ax, shard


@dataclass(frozen=True)
class PSpec:
    shape: tuple
    axes: tuple                 # logical axes, len == rank
    dtype: str = "bfloat16"
    init: str = "normal"        # normal | zeros | ones | small

    def sds(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def materialize(spec_tree, rng: jax.Array):
    """Random-init a PSpec tree (fan-in scaled normal)."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_pspec)
    keys = jax.random.split(rng, len(leaves))
    outs = []
    for spec, key in zip(leaves, keys, strict=True):
        if spec.init == "zeros":
            outs.append(jnp.zeros(spec.shape, spec.dtype))
        elif spec.init == "ones":
            outs.append(jnp.ones(spec.shape, spec.dtype))
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            scale = 0.02 if spec.init == "small" else 1.0 / math.sqrt(fan_in)
            outs.append((jax.random.normal(key, spec.shape, jnp.float32)
                         * scale).astype(spec.dtype))
    return jax.tree.unflatten(treedef, outs)


def abstract(spec_tree):
    return jax.tree.map(lambda s: s.sds(), spec_tree, is_leaf=is_pspec)


def axes_tree(spec_tree):
    return jax.tree.map(lambda s: ax(*s.axes), spec_tree, is_leaf=is_pspec)


def stack_specs(spec_tree, n: int):
    """Add a leading scan-period dimension (replicated) to every leaf."""
    return jax.tree.map(
        lambda s: PSpec((n, *s.shape), (None, *s.axes), s.dtype, s.init),
        spec_tree, is_leaf=is_pspec)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * (1.0 + scale.astype(dt))


def rmsnorm_spec(d: int) -> dict:
    return {"scale": PSpec((d,), (None,), "float32", "zeros")}


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- MLP variants ------------------------------------------------------------


def mlp_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp in ("swiglu", "geglu"):
        return {"wi": PSpec((d, f), ("embed", "ffn")),
                "wg": PSpec((d, f), ("embed", "ffn")),
                "wo": PSpec((f, d), ("ffn", "embed"))}
    if cfg.mlp in ("gelu", "squared_relu"):
        return {"wi": PSpec((d, f), ("embed", "ffn")),
                "wo": PSpec((f, d), ("ffn", "embed"))}
    if cfg.mlp == "none":
        return {}
    raise ValueError(f"unknown mlp kind {cfg.mlp!r}")


def mlp_apply(p: dict, x, cfg):
    """x: (B, S, D) → (B, S, D)."""
    if cfg.mlp == "none":
        return jnp.zeros_like(x)
    h = x @ p["wi"]
    h = shard(h, "batch", "seq", "ffn")
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * h
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(x @ p["wg"]) * h
    elif cfg.mlp == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.mlp == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    h = checkpoint_name(h, "mlp_hidden")
    out = h @ p["wo"]
    return shard(out, "batch", "seq", "embed_act")
