"""The public wrapper of the Pallas flash-attention kernel.

* auto-`interpret` on CPU (the kernel TARGETS TPU; interpret mode executes
  the kernel body in Python for correctness validation);
* `flash_attention` carries a custom_vjp wiring the recompute backward;
* model-facing layouts (B,S,H,hd) are adapted to kernel layouts here.
"""

from __future__ import annotations

import functools

import jax

from . import flash_attention as _fa


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention (custom_vjp)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=None, block_q=128,
                    block_k=128, interpret=None):
    """q: (B,S,H,hd); k: (B,T,Kv,hd); v: (B,T,Kv,hv).  Returns (B,S,H,hv)."""
    o, _ = _flash_fwd_impl(q, k, v, causal, window, block_q, block_k,
                           interpret)
    return o


def _heads_first(x):
    """(B, S, H, d) → (B·H, S, d)."""
    B, S, H, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, d)


def _heads_last(x, B):
    """(B·H, S, d) → (B, S, H, d)."""
    BH, S, d = x.shape
    return x.reshape(B, BH // B, S, d).transpose(0, 2, 1, 3)


def _flash_fwd_impl(q, k, v, causal, window, block_q, block_k, interpret):
    interpret = _default_interpret() if interpret is None else interpret
    of, lse = _fa.flash_attention_fwd(
        _heads_first(q), _heads_first(k), _heads_first(v), causal=causal,
        window=window, block_q=block_q, block_k=block_k, interpret=interpret)
    return _heads_last(of, q.shape[0]), lse


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, block_q, block_k,
                             interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    interpret_ = _default_interpret() if interpret is None else interpret
    dqf, dkf, dvf = _fa.flash_attention_bwd(
        *map(_heads_first, (q, k, v, o)), lse, _heads_first(do),
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret_)
    B = q.shape[0]
    return _heads_last(dqf, B), _heads_last(dkf, B), _heads_last(dvf, B)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
