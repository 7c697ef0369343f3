"""Attention mixers: GQA (full + sliding-window), chunked (flash-style)
variant, and MLA (multi-head latent attention) — plus single-token decode
steps against KV caches.

The chunked path is the jnp reference of the Pallas flash kernel
(kernels/flash_attention); the Pallas kernel swaps in on TPU via
``cfg.use_flash``, for GQA and for MLA (``_flash``).
"""

from __future__ import annotations

import math

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import current_mesh, prune_pspec, pspec, shard
from .layers import PSpec, apply_rope, rmsnorm

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# the flash kernel's tiles for MLA (qk 96, v 64): of 128-1024, 512 x 512 ran
# the forward and backward kernels fastest on a TPU v5e at 40 heads x 2048
MLA_FLASH_BLOCK_Q = 512
MLA_FLASH_BLOCK_K = 512


# ---------------------------------------------------------------------------
# GQA (full / sliding window)
# ---------------------------------------------------------------------------


def attn_specs(cfg) -> dict:
    d, hd = cfg.d_model, cfg.head_dim_
    H, Kv = cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": PSpec((d, H * hd), ("embed", "heads")),
        "wk": PSpec((d, Kv * hd), ("embed", "kv_heads")),
        "wv": PSpec((d, Kv * hd), ("embed", "kv_heads")),
        "wo": PSpec((H * hd, d), ("heads", "embed")),
    }


def _qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, Kv, hd)
    v = (x @ p["wv"]).reshape(B, S, Kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    q = checkpoint_name(q, "qkv")
    return q, k, v


def _causal_mask(S: int, T: int, window: int | None, q_offset: int = 0):
    qi = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0) + q_offset
    ki = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    m = ki <= qi
    if window is not None:
        m &= (qi - ki) < window
    return m


def _flash(q, k, v, window=None, block_q=128, block_k=128):
    """Causal attention through the Pallas flash kernel (interpret mode off a
    TPU).  q: (B,S,H,hd); k: (B,T,Kv,hd); v: (B,T,Kv,hv) → (B,S,H,hv).

    XLA cannot partition a Mosaic call, so under a mesh every device runs the
    kernel on its own block: the batch over the data axes, the heads over the
    model axis where that divides both H and Kv, else whole."""
    from ..kernels.ops import flash_attention

    def call(q, k, v):
        return flash_attention(q, k, v, True, window, block_q, block_k)

    mesh = current_mesh()
    if mesh is None:
        return call(q, k, v)

    def spec(x):
        s = tuple(prune_pspec(x.shape, pspec(("batch", None, "heads", None),
                                             mesh), mesh))
        return s + (None,) * (4 - len(s))

    qs, ks = spec(q), spec(k)
    if qs[2] != ks[2]:
        qs = ks = (qs[0], None, None, None)
    qs, ks = P(*qs), P(*ks)
    # the kernel's outputs carry no varying-axes (vma) annotation
    return jax.shard_map(call, mesh=mesh, in_specs=(qs, ks, ks),
                         out_specs=qs, check_vma=False)(q, k, v)


def gqa_attention(p, x, cfg, positions, window: int | None = None):
    """Training / prefill self-attention.  x: (B,S,D) → (B,S,D)."""
    B, S, D = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // Kv
    q, k, v = _qkv(p, x, cfg, positions)
    q = q.reshape(B, S, Kv, G, hd)

    if cfg.use_flash:
        # Pallas TPU kernel (kernels/flash_attention); interpret-mode on CPU
        if S % 128:
            raise ValueError(f"use_flash needs seq_len % 128 == 0, got {S}")
        qh = q.reshape(B, S, H, hd)
        ctx = _flash(qh, k, v, window).reshape(B, S, Kv, G, hd)
    elif cfg.attn_chunked and S > cfg.attn_chunk:
        ctx = _chunked_attention(q, k, v, cfg.attn_chunk, window,
                                 unroll=cfg.scan_unroll)
    else:
        scale = 1.0 / math.sqrt(hd)
        scores = jnp.einsum("bskgd,btkd->bkgst", q, k) * scale
        mask = _causal_mask(S, S, window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        probs = probs.astype(x.dtype)
        ctx = jnp.einsum("bkgst,btkd->bskgd", probs, v)

    ctx = ctx.reshape(B, S, H * hd)
    ctx = checkpoint_name(ctx, "attn_out")
    out = ctx @ p["wo"]
    return shard(out, "batch", "seq", "embed_act")


def _chunked_attention(q, k, v, chunk: int, window: int | None,
                       unroll: int = 1):
    """Flash-style online-softmax over key chunks (jnp reference of the
    Pallas kernel).  q: (B,S,Kv,G,hd); k/v: (B,T,Kv,hd)."""
    B, S, Kv, G, hd = q.shape
    T = k.shape[1]
    nc = T // chunk
    assert T % chunk == 0, (T, chunk)
    scale = 1.0 / math.sqrt(hd)
    kc = k.reshape(B, nc, chunk, Kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, Kv, hd).transpose(1, 0, 2, 3, 4)

    m0 = jnp.full((B, Kv, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kv, G, S), jnp.float32)
    a0 = jnp.zeros((B, S, Kv, G, hd), jnp.float32)

    def body(carry, inp):
        m, l, acc, ci = carry[0], carry[1], carry[2], carry[3]
        kb, vb = inp
        s = jnp.einsum("bskgd,btkd->bkgst", q, kb).astype(jnp.float32) * scale
        mask = _causal_mask(S, chunk, window, q_offset=0)
        # absolute key index = ci*chunk + t
        qi = jax.lax.broadcasted_iota(jnp.int32, (S, chunk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S, chunk), 1) + ci * chunk
        mask = ki <= qi
        if window is not None:
            mask &= (qi - ki) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(vb.dtype), vb)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new, ci + 1), None

    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, jnp.int32(0)),
                                     (kc, vc), unroll=min(unroll, nc))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype)


def attn_decode_step(p, x, k_cache, v_cache, pos, cfg,
                     window: int | None = None):
    """One-token decode.  x: (B,1,D); caches: (B,T,Kv,hd); pos: scalar int32
    (number of tokens already in cache).  Returns (y, k_cache, v_cache)."""
    B, _, D = x.shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // Kv
    T = k_cache.shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k = (x @ p["wk"]).reshape(B, 1, Kv, hd)
    v = (x @ p["wv"]).reshape(B, 1, Kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    slot = pos % T if window is not None else pos   # ring buffer for local
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, slot, 0, 0))

    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Kv, G, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg,
                        k_cache.astype(q.dtype)) * scale
    ti = jax.lax.iota(jnp.int32, T)
    valid = ti <= slot if window is None else \
        jnp.where(pos >= T, jnp.ones((T,), bool), ti <= slot)
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkgt,btkd->bkgd", probs, v_cache.astype(x.dtype))
    ctx = ctx.reshape(B, 1, H * hd)
    return ctx @ p["wo"], k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3 / DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_specs(cfg) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    return {
        "wdq": PSpec((d, m.q_lora_rank), ("embed", None)),
        "q_ln": PSpec((m.q_lora_rank,), (None,), "float32", "zeros"),
        "wuq": PSpec((m.q_lora_rank, H * m.qk_head_dim), (None, "heads")),
        "wdkv": PSpec((d, m.kv_lora_rank), ("embed", None)),
        "kv_ln": PSpec((m.kv_lora_rank,), (None,), "float32", "zeros"),
        "wkr": PSpec((d, m.qk_rope_dim), ("embed", None)),
        "wun": PSpec((m.kv_lora_rank, H * m.qk_nope_dim), (None, "heads")),
        "wuv": PSpec((m.kv_lora_rank, H * m.v_head_dim), (None, "heads")),
        "wo": PSpec((H * m.v_head_dim, d), ("heads", "embed")),
    }


def mla_attention(p, x, cfg, positions):
    """Training/prefill MLA.  K and V are expanded per head from the latent
    (``ckv``); the shared rope key is broadcast over the heads.  With
    ``cfg.use_flash`` and S a multiple of 128 the fused Pallas kernel runs
    (scores stay in VMEM); otherwise the S×S scores are materialised."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cq = rmsnorm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(B, S, H, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rmsnorm(x @ p["wdkv"], p["kv_ln"], cfg.norm_eps)   # (B,S,r)
    k_rope = apply_rope((x @ p["wkr"])[:, :, None, :], positions,
                        cfg.rope_theta)                       # (B,S,1,rd)
    k_nope = (ckv @ p["wun"]).reshape(B, S, H, m.qk_nope_dim)
    v = (ckv @ p["wuv"]).reshape(B, S, H, m.v_head_dim)

    if cfg.use_flash and S % 128 == 0:
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_dim))],
            axis=-1)
        with jax.named_scope("flash"):
            ctx = _flash(q, k, v, None, MLA_FLASH_BLOCK_Q, MLA_FLASH_BLOCK_K)
    else:
        scale = 1.0 / math.sqrt(m.qk_head_dim)
        scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope) +
                  jnp.einsum("bshd,btxd->bhst", q_rope, k_rope)) * scale
        mask = _causal_mask(S, S, None)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
        ctx = jnp.einsum("bhst,bthd->bshd", probs, v)
    ctx = checkpoint_name(ctx.reshape(B, S, H * m.v_head_dim), "attn_out")
    return shard(ctx @ p["wo"], "batch", "seq", "embed_act")


def mla_decode_step(p, x, ckv_cache, kr_cache, pos, cfg):
    """Absorbed-matrices MLA decode: attention runs in the latent space, so
    the cache is only (kv_lora_rank + rope_dim) per token."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    T = ckv_cache.shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)

    cq = rmsnorm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(B, 1, H, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_new = rmsnorm(x @ p["wdkv"], p["kv_ln"], cfg.norm_eps)     # (B,1,r)
    kr_new = apply_rope((x @ p["wkr"])[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]                 # (B,1,rd)
    ckv_cache = jax.lax.dynamic_update_slice(
        ckv_cache, ckv_new.astype(ckv_cache.dtype), (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        kr_cache, kr_new.astype(kr_cache.dtype), (0, pos, 0))

    # absorb W_un into the query side: q_lat (B,H,r)
    wun = p["wun"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wun)
    scale = 1.0 / math.sqrt(m.qk_head_dim)
    scores = (jnp.einsum("bhr,btr->bht", q_lat,
                         ckv_cache.astype(x.dtype)) +
              jnp.einsum("bhd,btd->bht", q_rope[:, 0],
                         kr_cache.astype(x.dtype))) * scale
    valid = jax.lax.iota(jnp.int32, T) <= pos
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
    ctx_lat = jnp.einsum("bht,btr->bhr", probs, ckv_cache.astype(x.dtype))
    wuv = p["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    ctx = jnp.einsum("bhr,rhd->bhd", ctx_lat, wuv).reshape(
        B, 1, H * m.v_head_dim)
    return ctx @ p["wo"], ckv_cache, kr_cache
