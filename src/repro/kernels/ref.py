"""Pure-jnp oracle for the Pallas flash-attention kernel (the allclose
ground truth)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def flash_attention_ref(q, k, v, causal: bool = True,
                        window: int | None = None, scale: float | None = None):
    """q: (B,S,H,hd); k: (B,T,Kv,hd); v: (B,T,Kv,hv) with H = Kv·G.  fp32
    softmax."""
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, Kv, G, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qi = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0) + (T - S)
    ki = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= (qi - ki) < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, v.shape[-1]).astype(q.dtype)
