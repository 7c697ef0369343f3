"""Mamba-2 mixer via SSD (state-space duality, arXiv:2405.21060).

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
compute *within* chunks (MXU-friendly) + a linear state recurrence *across*
chunks (lax.scan).  Decode is the O(1) recurrent step with a conv ring
buffer and the SSM state as cache.  All cumulative/decay terms in fp32.
"""

from __future__ import annotations

import math

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import (current_mesh, prune_pspec, pspec,
                                    resolve_axes, shard)
from .layers import PSpec, rmsnorm


def ssm_specs(cfg) -> dict:
    s = cfg.ssm
    d, di = cfg.d_model, cfg.d_inner
    nh = cfg.ssm_heads
    gn = 2 * s.n_groups * s.d_state
    return {
        "wz": PSpec((d, di), ("embed", "ffn")),
        "wx": PSpec((d, di), ("embed", "ffn")),
        "wbc": PSpec((d, gn), ("embed", None)),
        "wdt": PSpec((d, nh), ("embed", "ffn")),
        "conv_x": PSpec((s.conv_width, di), (None, "ffn"), "float32"),
        "conv_bc": PSpec((s.conv_width, gn), (None, None), "float32"),
        "A_log": PSpec((nh,), ("ffn",), "float32", "zeros"),
        "dt_bias": PSpec((nh,), ("ffn",), "float32", "zeros"),
        "D": PSpec((nh,), ("ffn",), "float32", "ones"),
        "norm": PSpec((di,), ("ffn",), "float32", "zeros"),
        "wo": PSpec((di, d), ("ffn", "embed")),
    }


def _causal_conv(u, w):
    """Depthwise causal conv along axis 1.  u: (B,S,C); w: (cw,C)."""
    cw = w.shape[0]
    out = u * w[-1]
    for i in range(1, cw):
        shifted = jnp.pad(u, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :u.shape[1]]
        out = out + shifted * w[-1 - i]
    return out


def _in_proj(p: dict, x):
    """The column-parallel in-projections x @ wz, x @ wx, x @ wdt.

    Each weight's columns are split over the mesh axes of ``ffn``, so each
    product's input gradient is a partial sum over them.  Where those axes
    divide every projection's width, the products run in a ``shard_map``
    that takes x whole over them: the backward adds the three local input
    gradients on each chip and reduces that one sum with a single psum,
    where the partitioner would all-reduce each partial on its own (3x the
    bytes).  The weights enter gathered over their FSDP (``embed``) axes,
    so those gathers and the reduce-scatters of the weights' gradients stay
    the partitioner's.  Otherwise (no mesh, one device) the plain products."""
    ws = p["wz"], p["wx"], p["wdt"]
    mesh = current_mesh()
    axes = resolve_axes("ffn", mesh) if mesh is not None else ()
    n = math.prod(int(mesh.shape[a]) for a in axes)
    if n == 1 or any(w.shape[1] % n for w in ws):
        return tuple(x @ w for w in ws)

    x_spec = prune_pspec(x.shape, pspec(("batch", None, None), mesh), mesh)
    w_spec = pspec((None, "ffn"), mesh)
    out_spec = P(x_spec[0] if len(x_spec) else None, None, w_spec[1])

    def body(x, *ws):
        # x made varying once, so the transpose psums the sum of the three;
        # each weight promoted before it meets the batch's axes, so its
        # gradient is summed over them in the product's dtype
        x = jax.lax.pcast(x, axes, to="varying")
        return tuple(x @ w.astype(jnp.result_type(x, w)) for w in ws)

    return jax.shard_map(body, mesh=mesh, in_specs=(x_spec, *(w_spec,) * 3),
                         out_specs=(out_spec,) * 3)(x, *ws)


def ssd_apply(p: dict, x, cfg):
    """Full-sequence SSD.  x: (B,S,D) → (B,S,D)."""
    s = cfg.ssm
    B_, S, D = x.shape
    di, nh, hp, N, G = (cfg.d_inner, cfg.ssm_heads, s.headdim, s.d_state,
                        s.n_groups)
    Q = min(s.chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    with jax.named_scope("in_proj"):
        z, xin, dt_in = _in_proj(p, x)
    bc = x @ p["wbc"]
    with jax.named_scope("conv"):
        xin = jax.nn.silu(_causal_conv(xin.astype(jnp.float32),
                                       p["conv_x"])).astype(x.dtype)
        bc = jax.nn.silu(_causal_conv(bc.astype(jnp.float32),
                                      p["conv_bc"])).astype(x.dtype)
    xin = shard(xin, "batch", "seq", "ffn")
    Bm, Cm = jnp.split(bc.reshape(B_, S, 2 * G, N), 2, axis=2)   # (B,S,G,N)
    dt = jax.nn.softplus(dt_in.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["A_log"])                                      # (nh,)

    xh = xin.reshape(B_, S, nh, hp)
    rep = nh // G
    Bh = jnp.repeat(Bm, rep, axis=2)                              # (B,S,nh,N)
    Ch = jnp.repeat(Cm, rep, axis=2)

    # chunked views
    def ch(t):
        return t.reshape(B_, nc, Q, *t.shape[2:])

    xc, dtc, Bc, Cc = ch(xh), ch(dt), ch(Bh), ch(Ch)
    dA = dtc * A                                                  # (B,nc,Q,nh)
    cum = jnp.cumsum(dA, axis=2)                                  # (B,nc,Q,nh)
    total = cum[:, :, -1]                                         # (B,nc,nh)
    dtx = xc * dtc[..., None].astype(xc.dtype)                    # (B,nc,Q,nh,hp)

    # intra-chunk (quadratic, masked decay kernel).  The mask goes inside the
    # exponential: above the diagonal li - lj sums -dt*A over up to Q-1
    # positions and exp overflows to inf, and the backward pass of a where
    # applied after exp multiplies its zero cotangent by that inf (NaN).
    with jax.named_scope("intra"):
        li = cum[:, :, :, None, :]                                # i
        lj = cum[:, :, None, :, :]                                # j
        mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, ..., None]
        decay = jnp.exp(jnp.where(mask, li - lj, -jnp.inf))       # (B,nc,Q,Q,nh)
        cb = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc).astype(jnp.float32)
        att = cb * decay
        y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att.astype(xc.dtype), dtx)

    # chunk summary states: (B,nc,nh,hp,N)
    with jax.named_scope("states"):
        sdecay = jnp.exp(total[:, :, None] - cum)                 # (B,nc,Q,nh)
        states = jnp.einsum("bcjhn,bcjhp->bchpn",
                            (Bc.astype(jnp.float32) *
                             sdecay[..., None]).astype(xc.dtype), dtx)

    # inter-chunk recurrence
    def step(carry, inp):
        st_prev = carry
        st_c, tot_c = inp
        new = st_prev * jnp.exp(tot_c)[:, :, None, None] + st_c
        return new, st_prev

    with jax.named_scope("scan"):
        init = jnp.zeros((B_, nh, hp, N), jnp.float32)
        _, prev_states = jax.lax.scan(
            step, init, (states.astype(jnp.float32).transpose(1, 0, 2, 3, 4),
                         total.transpose(1, 0, 2)),
            unroll=min(cfg.scan_unroll, nc))
        prev_states = prev_states.transpose(1, 0, 2, 3, 4)        # (B,nc,nh,hp,N)

    with jax.named_scope("inter"):
        y_inter = jnp.einsum("bcihn,bchpn->bcihp",
                             (Cc.astype(jnp.float32) *
                              jnp.exp(cum)[..., None]).astype(xc.dtype),
                             prev_states.astype(xc.dtype))
    y = (y_intra + y_inter).reshape(B_, S, nh, hp)
    y = y + xh * p["D"][..., None].astype(xh.dtype)
    y = y.reshape(B_, S, di)
    y = checkpoint_name(y, "ssm_state")

    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["norm"], cfg.norm_eps)
    return shard(y @ p["wo"], "batch", "seq", "embed_act")


def ssd_decode_step(p: dict, x, conv_cache, state, cfg):
    """One-token recurrent step.
    x: (B,1,D); conv_cache: (B,cw-1,di+2GN) fp32; state: (B,nh,hp,N) fp32."""
    s = cfg.ssm
    B_ = x.shape[0]
    di, nh, hp, N, G = (cfg.d_inner, cfg.ssm_heads, s.headdim, s.d_state,
                        s.n_groups)
    z = x @ p["wz"]                                    # (B,1,di)
    xin = x @ p["wx"]
    bc = x @ p["wbc"]
    u = jnp.concatenate([xin, bc], axis=-1).astype(jnp.float32)  # (B,1,ch)
    win = jnp.concatenate([conv_cache, u], axis=1)               # (B,cw,ch)
    w = jnp.concatenate([p["conv_x"], p["conv_bc"]], axis=1)     # (cw,ch)
    conv_out = jnp.einsum("bcf,cf->bf", win, w)
    conv_out = jax.nn.silu(conv_out)
    new_conv_cache = win[:, 1:]

    xin_c, bc_c = conv_out[:, :di], conv_out[:, di:]
    Bm, Cm = jnp.split(bc_c.reshape(B_, 2 * G, N), 2, axis=1)    # (B,G,N)
    rep = nh // G
    Bh = jnp.repeat(Bm, rep, axis=1)                             # (B,nh,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    dt = jax.nn.softplus(
        (x[:, 0] @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])  # (B,nh)
    A = -jnp.exp(p["A_log"])
    xh = xin_c.reshape(B_, nh, hp).astype(jnp.float32)

    decay = jnp.exp(dt * A)                                      # (B,nh)
    state = state * decay[..., None, None] + \
        jnp.einsum("bh,bhp,bhn->bhpn", dt, xh, Bh.astype(jnp.float32))
    y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), state)
    y = y + xh * p["D"][:, None]
    y = y.reshape(B_, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["norm"], cfg.norm_eps)
    return y @ p["wo"], new_conv_cache, state
