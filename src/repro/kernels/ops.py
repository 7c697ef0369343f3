"""jit'd public wrappers for the Pallas kernels.

* auto-`interpret` on CPU (the kernels TARGET TPU; interpret mode executes
  the kernel body in Python for correctness validation);
* block sizes are halved until they divide the tensor, and refused when
  that leaves a block the TPU's (8, 128) tiling rule rejects;
* `flash_attention` carries a custom_vjp wiring the recompute backward;
* model-facing layouts (B,S,H,hd) are adapted to kernel layouts here.
"""

from __future__ import annotations

import functools

import jax

from . import flash_attention as _fa
from . import fused_adam as _ad
from . import rmsnorm as _rn


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention (custom_vjp)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=None, block_q=128,
                    block_k=128, interpret=None):
    """q: (B,S,H,hd); k/v: (B,T,Kv,hd).  Returns (B,S,H,hd)."""
    o, _ = _flash_fwd_impl(q, k, v, causal, window, block_q, block_k,
                           interpret)
    return o


def _fold(q, k, v):
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kv, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kv, T, hd)
    return qf, kf, vf


def _flash_fwd_impl(q, k, v, causal, window, block_q, block_k, interpret):
    interpret = _default_interpret() if interpret is None else interpret
    B, S, H, hd = q.shape
    qf, kf, vf = _fold(q, k, v)
    of, lse = _fa.flash_attention_fwd(qf, kf, vf, causal=causal,
                                      window=window, block_q=block_q,
                                      block_k=block_k, interpret=interpret)
    o = of.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return o, lse


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, block_q, block_k,
                             interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    interpret_ = _default_interpret() if interpret is None else interpret
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    qf, kf, vf = _fold(q, k, v)
    of = o.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    dof = do.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    dqf, dkf, dvf = _fa.flash_attention_bwd(
        qf, kf, vf, of, lse, dof, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret_)
    dq = dqf.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    dk = dkf.reshape(B, Kv, T, hd).transpose(0, 2, 1, 3)
    dv = dvf.reshape(B, Kv, T, hd).transpose(0, 2, 1, 3)
    return dq, dk, dv


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# rmsnorm / fused adam
# ---------------------------------------------------------------------------


def _divisor_block(n: int, block: int, align: int) -> int:
    """Largest ``block / 2**k`` dividing ``n`` (``n`` itself when smaller).
    The result must be a multiple of ``align`` or the whole of ``n``."""
    b = min(block, n)
    while n % b:
        b //= 2
    if b % align and b != n:
        raise ValueError(f"no {align}-aligned block of at most {block} "
                         f"divides {n} (largest divisor found: {b})")
    return b


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, block_rows: int = 256,
            interpret=None):
    """x: (..., d)."""
    interpret = _default_interpret() if interpret is None else interpret
    shp = x.shape
    rows = 1
    for s in shp[:-1]:
        rows *= s
    x2 = x.reshape(rows, shp[-1])
    br = _divisor_block(rows, block_rows, 8)
    out = _rn.rmsnorm(x2, scale, eps=eps, block_rows=br,
                      interpret=interpret)
    return out.reshape(shp)


def fused_adam(p, g, m, v, count, lr, b1=0.9, b2=0.95, eps=1e-8,
               weight_decay=0.0, interpret=None):
    """Pytree-leaf AdamW step via the fused kernel; any shape (flattened)."""
    interpret = _default_interpret() if interpret is None else interpret
    shp = p.shape
    n = p.size
    block = _divisor_block(n, 65536, 128)
    out = _ad.fused_adam(p.reshape(n), g.reshape(n), m.reshape(n),
                         v.reshape(n), count, lr=lr, b1=b1, b2=b2, eps=eps,
                         weight_decay=weight_decay, block=block,
                         interpret=interpret)
    return tuple(t.reshape(shp) for t in out)


def ssd_chunk(x, dt, b, c, a, interpret=None):
    """Fused SSD intra-chunk (Mamba-2) — see kernels/ssd_chunk.py."""
    from . import ssd_chunk as _sc
    interpret = _default_interpret() if interpret is None else interpret
    return _sc.ssd_chunk(x, dt, b, c, a, interpret=interpret)
