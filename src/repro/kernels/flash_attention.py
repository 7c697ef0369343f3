"""FlashAttention for TPU in Pallas — the paper's layer-fusion flagship
(§II-C2): QKᵀ → masked online softmax → PV fused in VMEM, never writing the
S×T score matrix to HBM.

TPU adaptation (vs the CUDA original): tiling is chosen for the 128×128 MXU
and VMEM residency instead of warps/shared-memory banking — q blocks of
``block_q`` rows stream from HBM→VMEM via BlockSpec; the full K/V stripe for
one (batch, kv-head) lives in VMEM (seq·hd·2·2 B ≤ a few MB for 32 k ctx);
the kv loop is a ``fori_loop`` over ``block_k`` tiles with causality-pruned
trip count.  GQA is handled by the BlockSpec index map (q-head i reads
kv-head i//G) — no repeated K/V in HBM.

q and k share one head dim; v may have its own (MLA: qk 96, v 64).  The
output and dv take v's, dq and dk q's; the default scale is 1/√(q's).

The MXU is fed in the inputs' own dtype with f32 accumulation: for bf16
inputs the probabilities p and their gradient ds are cast to bf16 before
their products, while scores, softmax statistics and accumulators stay f32.
f32 inputs keep f32 operands.

Backward is the standard two-kernel recompute scheme (dq then dk/dv) using
the saved per-row logsumexp.

Per-row statistics (logsumexp, delta) cross the kernel boundary as
``(BH, S, LANES)`` arrays with the value repeated across the 128 lanes: a
``(1, block_q)`` block of a ``(BH, S)`` array breaks the TPU's (8, 128)
tiling rule, while ``(1, block_q, LANES)`` meets it.  Row vectors inside the
kernels are kept 2-D, ``(rows, 1)``, for the same reason.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def _dot_nt(a, b):
    """a @ b.T with fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b with fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    """a @ b with fp32 accumulation."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _masked(s, q0, k0, causal, window, q_offset):
    """Scores ``s`` of queries ``q0 + i`` against keys ``k0 + j``, with
    NEG_INF where the key is hidden from the query."""
    qi = q0 + q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    ki = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    m = None
    if causal:
        m = ki <= qi
    if window is not None:
        w = qi - ki < window
        m = w if m is None else (m & w)
    return s if m is None else jnp.where(m, s, NEG_INF)


def _kv_tiles(q0, bq, nk, bk, causal, q_offset):
    """Key tiles ``[0, n)`` hold every key the query rows ``q0 .. q0 + bq``
    can see; causality prunes those past the last row."""
    if not causal:
        return nk
    return jnp.minimum(nk, pl.cdiv(q0 + bq + q_offset, bk))


def _lanes(x):
    """(BH, S) row statistic → (BH, S, LANES), repeated across lanes."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                window, block_k, q_offset):
    bq, hv = q_ref.shape[1], v_ref.shape[2]
    T = k_ref.shape[1]
    q = q_ref[0]                                       # (bq, hd)
    q0 = pl.program_id(1) * bq
    nk = _kv_tiles(q0, bq, T // block_k, block_k, causal, q_offset)

    def body(i, carry):
        m, l, acc = carry                              # (bq,1) (bq,1) (bq,hv)
        kb = k_ref[0, pl.ds(i * block_k, block_k), :]
        vb = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _masked(_dot_nt(q, kb) * scale, q0, i * block_k, causal, window,
                    q_offset)                          # (bq, bk) f32
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + _dot(p.astype(vb.dtype), vb)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, hv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (bq, LANES))


def flash_attention_fwd(q, k, v, *, causal=True, window=None, scale=None,
                        block_q=128, block_k=128, interpret=False):
    """q: (BH, S, hd); k: (BKv, T, hd); v: (BKv, T, hv).  G = BH // BKv per
    batch-head grouping must already be arranged so q row i maps to kv row
    i // G.  Returns (o, lse) with o: (BH, S, hv), lse: (BH, S) fp32."""
    BH, S, hd = q.shape
    BKv, T, _ = k.shape
    hv = v.shape[2]
    G = BH // BKv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0
    grid = (BH, S // block_q)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, block_k=block_k,
                               q_offset=T - S)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, T, hd), lambda i, j: (i // G, 0, 0)),
            pl.BlockSpec((1, T, hv), lambda i, j: (i // G, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward (recompute scheme)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, window, block_k, q_offset):
    bq, hd = q_ref.shape[1], q_ref.shape[2]
    T = k_ref.shape[1]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, :1]                           # (bq, 1)
    delta = delta_ref[0][:, :1]
    q0 = pl.program_id(1) * bq
    nk = _kv_tiles(q0, bq, T // block_k, block_k, causal, q_offset)

    def body(i, dq):
        kb = k_ref[0, pl.ds(i * block_k, block_k), :]
        vb = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _masked(_dot_nt(q, kb) * scale, q0, i * block_k, causal, window,
                    q_offset)
        p = jnp.exp(s - lse)                          # (bq, bk)
        dp = _dot_nt(do, vb)
        ds = p * (dp - delta) * scale
        return dq + _dot(ds.astype(kb.dtype), kb)

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, hd), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, window, block_q, q_offset):
    bk, hd, hv = k_ref.shape[1], k_ref.shape[2], v_ref.shape[2]
    S = q_ref.shape[1]
    k = k_ref[0]
    v = v_ref[0]
    k0 = pl.program_id(1) * bk
    # query tiles before this key tile see none of it
    start = jnp.maximum((k0 - q_offset) // block_q, 0) if causal else 0

    def body(j, carry):
        dk, dv = carry
        rows = pl.ds(j * block_q, block_q)
        qb = q_ref[0, rows, :]
        dob = do_ref[0, rows, :]
        lseb = lse_ref[0, rows, :][:, :1]
        deltab = delta_ref[0, rows, :][:, :1]
        s = _masked(_dot_nt(qb, k) * scale, j * block_q, k0, causal, window,
                    q_offset)                          # (bq, bk)
        p = jnp.exp(s - lseb)
        dv_new = dv + _dot_tn(p.astype(dob.dtype), dob)
        dp = _dot_nt(dob, v)
        ds = p * (dp - deltab) * scale
        dk_new = dk + _dot_tn(ds.astype(qb.dtype), qb)
        return dk_new, dv_new

    z = (jnp.zeros((bk, hd), jnp.float32), jnp.zeros((bk, hv), jnp.float32))
    dk, dv = jax.lax.fori_loop(start, S // block_q, body, z)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=None,
                        scale=None, block_q=128, block_k=128,
                        interpret=False):
    """Gradients of flash_attention_fwd; lse: (BH, S) as it returned."""
    BH, S, hd = q.shape
    BKv, T, _ = k.shape
    hv = v.shape[2]
    G = BH // BKv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse, delta = _lanes(lse), _lanes(delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, block_k=block_k, q_offset=T - S),
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, T, hd), lambda i, j: (i // G, 0, 0)),
            pl.BlockSpec((1, T, hv), lambda i, j: (i // G, 0, 0)),
            pl.BlockSpec((1, block_q, hv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv computed per q-head then reduced over the GQA group
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, q_offset=T - S),
        grid=(BH, T // block_k),
        in_specs=[
            pl.BlockSpec((1, S, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, j: (i // G, j, 0)),
            pl.BlockSpec((1, block_k, hv), lambda i, j: (i // G, j, 0)),
            pl.BlockSpec((1, S, hv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, S, LANES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, S, LANES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, hv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, T, hv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(BKv, G, T, hd).sum(axis=1).astype(k.dtype)
    dv = dv_h.reshape(BKv, G, T, hv).sum(axis=1).astype(v.dtype)
    return dq, dk, dv
