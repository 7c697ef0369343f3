"""LM loss: sharded-vocab cross-entropy with optional **chunked fused
unembedding** — the (B,S,V) logits tensor is never materialized; the final
projection + softmax-xent run per sequence chunk inside a scan.  At
nemotron-4-340b scale (V=256000) this removes a multi-GB transient and is
one of the beyond-paper memory optimizations recorded in EXPERIMENTS §Perf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.sharding import shard
from ..models.transformer import forward_hidden, unembed_weight

Z_LOSS = 1e-4
AUX_LOSS = 1e-2


def _xent_from_logits(logits, labels):
    """logits: (..., V) any sharding; labels: (...) int32.
    Returns (nll, z) with stable fp32 logsumexp."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    label_logit = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = lse - label_logit
    return nll, jnp.square(lse)


def lm_loss(params, cfg, inputs, labels, loss_chunk: int | None = None):
    """Returns (loss, metrics).  labels: (B,S) int32, -1 = masked."""
    hidden, aux = forward_hidden(params, cfg, inputs)
    with jax.named_scope("loss"):
        w = unembed_weight(params, cfg)
        B, S, D = hidden.shape
        mask = (labels >= 0).astype(jnp.float32)
        safe_labels = jnp.maximum(labels, 0)

        chunk = loss_chunk if loss_chunk is not None else cfg.loss_chunk
        if chunk == 0:  # auto: chunk when the logits tensor would be > 2^28 elems
            chunk = S // 8 if S * cfg.vocab > (1 << 28) and S % 8 == 0 else 0

        if chunk and S % chunk == 0 and S > chunk:
            nc = S // chunk
            hc = hidden.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
            lc = safe_labels.reshape(B, nc, chunk).transpose(1, 0, 2)
            mc = mask.reshape(B, nc, chunk).transpose(1, 0, 2)

            @jax.checkpoint   # recompute chunk logits in bwd: never keep (B,c,V)
            def chunk_nll(h, lab, msk):
                logits = h @ w                       # (B, chunk, V) transient
                logits = shard(logits, "batch", "seq", "vocab")
                nll, z = _xent_from_logits(logits, lab)
                return jnp.sum(nll * msk), jnp.sum(z * msk)

            def body(carry, xs):
                nll_sum, z_sum = carry
                dn, dz = chunk_nll(*xs)
                return (nll_sum + dn, z_sum + dz), None

            (nll_sum, z_sum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                (hc, lc, mc), unroll=min(cfg.scan_unroll, nc))
        else:
            logits = hidden @ w
            logits = shard(logits, "batch", "seq", "vocab")
            nll, z = _xent_from_logits(logits, safe_labels)
            nll_sum = jnp.sum(nll * mask)
            z_sum = jnp.sum(z * mask)

    denom = jnp.maximum(mask.sum(), 1.0)
    nll_mean = nll_sum / denom
    loss = nll_mean + Z_LOSS * z_sum / denom + AUX_LOSS * aux
    metrics = {"loss": loss, "nll": nll_mean, "aux_loss": aux,
               "tokens": denom}
    return loss, metrics
