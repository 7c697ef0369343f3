"""Fused SSD intra-chunk kernel (Mamba-2 state-space duality) in Pallas.

The quadratic-within-chunk part of SSD is the attention-analogue hot loop
for the attention-free archs (mamba2-1.3b, jamba's mamba layers): per
(batch·head, chunk) it computes, entirely in VMEM,

    cum     = cumsum(dt)·A                                (Q,)
    L       = tril(exp(cum_i − cum_j))                    (Q,Q)  decay kernel
    y_intra = ((C Bᵀ) ⊙ L) @ (x·dt)                       (Q,hp)
    states  = (B · exp(cum_Q − cum))ᵀ @ (x·dt)            (N,hp) chunk summary

— one HBM round-trip for x/B/C/dt instead of five for the unfused chain,
and the (Q,Q) decay/attention matrices never leave VMEM.  The (linear)
inter-chunk recurrence and Y_inter stay in jnp (lax.scan), exactly like the
model's reference path in models/ssm.py.

Q is the chunk (128/256 → MXU-aligned); hp, N are 64/128 → lane-aligned.

``cum`` is an O(Q) prefix sum per chunk, so it is computed before the call
and enters twice, as a (Q, 1) column and a (1, Q) row: the decay matrix is
their difference, with no in-kernel cumsum or transpose.  ``dt`` enters as
a (Q, 1) column.  Both layouts meet the TPU's (8, 128) block-tiling rule,
which a (1, Q) block of a (BH, nc, Q) array does not.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref,
                      y_ref, st_ref):
    Q = x_ref.shape[2]
    x = x_ref[0, 0].astype(jnp.float32)           # (Q, hp)
    dt = dt_ref[0, 0]                             # (Q, 1)
    cum_c = cumc_ref[0, 0]                        # (Q, 1)
    cum_r = cumr_ref[0, 0]                        # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)           # (Q, N)

    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # masked before exp: above the diagonal cum_c - cum_r is positive and
    # grows with the chunk, so exp would overflow to inf there
    decay = jnp.exp(jnp.where(qi >= ki, cum_c - cum_r, -jnp.inf))

    att = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * decay
    dtx = x * dt
    y = jnp.dot(att, dtx, preferred_element_type=jnp.float32)   # (Q, hp)

    sdecay = jnp.exp(cum_c[Q - 1:, :] - cum_c)    # (Q, 1)
    states = jax.lax.dot_general(b * sdecay, dtx, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = states.astype(st_ref.dtype)


def ssd_chunk(x, dt, b, c, a, *, interpret=False):
    """x: (BH, nc, Q, hp); dt: (BH, nc, Q); b/c: (BH, nc, Q, N);
    a: (BH,) negative decay rates.  Returns (y_intra, states, cum):
    (BH,nc,Q,hp), (BH,nc,N,hp) fp32, (BH,nc,Q) fp32."""
    BH, nc, Q, hp = x.shape
    N = b.shape[-1]
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(dt, axis=2) * a[:, None, None]
    y, states = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, hp), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, hp), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, N, hp), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, hp), x.dtype),
            jax.ShapeDtypeStruct((BH, nc, N, hp), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt[..., None], cum[..., None], cum[..., None, :], b, c)
    return y, states, cum
