"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

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


def rmsnorm_ref(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) *
            (1.0 + scale.astype(jnp.float32))).astype(dt)


def fused_adam_ref(p, g, m, v, *, lr, b1=0.9, b2=0.95, eps=1e-8,
                   weight_decay=0.0, count=1):
    """One AdamW step on a flat tensor; states fp32; returns (p', m', v')."""
    g32 = g.astype(jnp.float32)
    m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
    v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count
    upd = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps)
    p32 = p.astype(jnp.float32)
    p32 = p32 - lr * (upd + weight_decay * p32)
    return p32.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)


def ssd_chunk_ref(x, dt, b, c, a):
    """Oracle for kernels/ssd_chunk: x (BH,nc,Q,hp); dt (BH,nc,Q);
    b/c (BH,nc,Q,N); a (BH,).  Returns (y_intra, states, cum)."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    cum = jnp.cumsum(dtf, axis=2) * a[:, None, None]          # (BH,nc,Q)
    Q = x.shape[2]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # masked before exp, which overflows above the diagonal
    decay = jnp.exp(jnp.where(mask, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    att = jnp.einsum("hcin,hcjn->hcij", cf, bf) * decay
    dtx = xf * dtf[..., None]
    y = jnp.einsum("hcij,hcjp->hcip", att, dtx)
    sdecay = jnp.exp(cum[..., -1:] - cum)                     # (BH,nc,Q)
    states = jnp.einsum("hcjn,hcjp->hcnp", bf * sdecay[..., None], dtx)
    return y.astype(x.dtype), states, cum
