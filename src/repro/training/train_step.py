"""train_step / serve_step factories with microbatched gradient accumulation.

``make_train_step`` returns a pure function
``(params, opt_state, batch, step) -> (params, opt_state, metrics)`` suitable
for ``jax.jit`` with donated params/opt_state.  Gradient accumulation runs as
a ``lax.scan`` over microbatches — XLA's latency-hiding scheduler overlaps
each microbatch's gradient all-reduce with the next one's backward pass.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..optim.optimizers import Optimizer, clip_by_global_norm
from .loss import lm_loss


def make_train_step(cfg, optimizer: Optimizer, grad_accum: int = 1,
                    clip_norm: float = 1.0, accum_dtype: str = "float32"):
    def loss_fn(params, inputs, labels):
        return lm_loss(params, cfg, inputs, labels)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch, step):
        inputs, labels = batch["inputs"], batch["labels"]
        if grad_accum == 1:
            (loss, metrics), grads = grad_fn(params, inputs, labels)
        else:
            B = inputs.shape[0]
            mb = B // grad_accum
            ishape = (grad_accum, mb) + inputs.shape[1:]
            lshape = (grad_accum, mb) + labels.shape[1:]
            mi = inputs.reshape(ishape)
            ml = labels.reshape(lshape)

            adt = jnp.dtype(accum_dtype)

            def body(acc, xs):
                g_acc, l_acc = acc
                (l, _), g = grad_fn(params, xs[0], xs[1])
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(adt), g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
            (grads, loss_sum), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), (mi, ml),
                unroll=min(cfg.scan_unroll, grad_accum))
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) / grad_accum), grads)
            loss = loss_sum / grad_accum
            metrics = {"loss": loss}

        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = optimizer.update(grads, opt_state,
                                                         params, step)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr_step"] = jnp.asarray(step, jnp.int32)
        return new_params, new_opt_state, metrics

    return train_step


def make_eval_step(cfg):
    def eval_step(params, batch):
        _, metrics = lm_loss(params, cfg, batch["inputs"], batch["labels"])
        return metrics
    return eval_step


def make_serve_step(cfg, sample: str = "greedy", temperature: float = 1.0):
    """Returns (params, cache, inputs, pos, rng) -> (next_tokens, new_cache).
    inputs: (B,1) tokens or (B,1,D) embeddings."""
    from ..models.transformer import decode_step

    def serve_step(params, cache, inputs, pos, rng=None):
        logits, new_cache = decode_step(params, cache, cfg, inputs, pos)
        logits = logits[:, -1]
        if sample == "greedy":
            nxt = jnp.argmax(logits, axis=-1)
        else:
            nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
        return nxt.astype(jnp.int32), new_cache

    return serve_step
