"""Plain float32 reference of a training step, shared by the configurations.

Nothing here imports the program.  A configuration's reference module
(``configs/<name>.py``) describes its parameters as a tree of ``Leaf`` and
gives ``embed``, ``layer`` and ``head`` functions in plain ``jax.numpy``;
``train`` runs three steps of the same job the benchmark times: the loss of
the copy objective, the global-norm clip, AdamW with the warm-up schedule,
parameters stored in the dtype the configuration states.

The step runs layer by layer (forward keeps each layer's input, backward
recomputes one layer at a time), so a model whose state fills a chip still
fits: only one layer's activations live at once.  With several devices the
layers' state is spread over them round-robin.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# The training job, as the benchmark runs it (Trainer's defaults).
LR, WARMUP, TOTAL, MIN_FRAC = 3e-4, 100, 10000, 0.1
B1, B2, EPS, WD = 0.9, 0.95, 1e-8, 0.01
CLIP = 1.0
Z_LOSS = 1e-4
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Leaf(NamedTuple):
    """One parameter: shape, stored dtype, init ('normal' | 'small' |
    'zeros' | 'ones')."""
    shape: tuple
    dtype: str
    init: str = "normal"


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


# ---------------------------------------------------------------------------
# weights and tokens from the seed
# ---------------------------------------------------------------------------


def init_params(specs, seed: int):
    """One normal draw per leaf from ``split(PRNGKey(seed), n_leaves)`` in
    the tree's key order, scaled by 1/sqrt(fan_in) ('normal') or 0.02
    ('small'), then cast to the stored dtype."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_leaf)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for spec, k in zip(leaves, keys, strict=True):
            if spec.init == "zeros":
                out.append(jnp.zeros(spec.shape, spec.dtype))
            elif spec.init == "ones":
                out.append(jnp.ones(spec.shape, spec.dtype))
            else:
                fan_in = spec.shape[-2] if len(spec.shape) >= 2 \
                    else spec.shape[-1]
                scale = 0.02 if spec.init == "small" \
                    else 1.0 / math.sqrt(fan_in)
                out.append((jax.random.normal(k, spec.shape, F32) * scale)
                           .astype(spec.dtype))
        return out

    return jax.tree.unflatten(treedef, make(jax.random.PRNGKey(seed)))


def tokens(vocab: int, batch: int, seq: int, step: int, seed: int
           ) -> np.ndarray:
    """The synthetic token stream: a counter hash of (seed, step, index),
    modulo the vocabulary.  Labels are the tokens themselves."""
    ctr = (np.arange(batch * seq, dtype=np.uint64)
           + np.uint64(step) * np.uint64(batch * seq))
    salt = np.uint64(((seed + 1) * 0x9E3779B97F4A7C15) % (1 << 64))
    with np.errstate(over="ignore"):
        x = ctr + salt
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xFF51AFD7ED558CCD)
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xC4CEB9FE1A85EC53)
        x ^= x >> np.uint64(33)
    x = (x & np.uint64(0xFFFFFFFF)).astype(np.uint32) % np.uint32(vocab)
    return x.reshape(batch, seq).astype(np.int32)


def lr_at(step: int) -> float:
    if step < WARMUP:
        return LR * min(1.0, (step + 1) / WARMUP)
    t = min(max((step - WARMUP) / (TOTAL - WARMUP), 0.0), 1.0)
    return LR * (MIN_FRAC + (1 - MIN_FRAC) * 0.5 * (1 + math.cos(math.pi * t)))


# ---------------------------------------------------------------------------
# arithmetic: float32 at full precision, or the fp8 control
# ---------------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3 with one per-tensor scale (amax -> 448); the
    backward pass sees the rounded value (straight-through)."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


@dataclass(frozen=True)
class Arith:
    """How the reference multiplies: ``fp8`` rounds both operands of every
    product to float8 first (the control); ``f32`` does not."""
    fp8: bool = False

    def ein(self, spec: str, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self.fp8:
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    """RMSNorm with a (1 + scale) gain."""
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def rope(x, positions, theta):
    """Rotary embedding, rotate-half layout.  x: (..., S, H, d)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = positions[:, None].astype(F32) * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def xent_sums(logits, labels, mask):
    """(sum of masked nll, sum of masked lse^2) over (S, V) logits."""
    m = jax.lax.stop_gradient(jnp.max(logits, -1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), -1)) + m[:, 0]
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum((lse - picked) * mask), jnp.sum(jnp.square(lse) * mask)


# ---------------------------------------------------------------------------
# one training step, layer by layer
# ---------------------------------------------------------------------------


class Model(NamedTuple):
    """What a configuration's reference module provides.

    ``specs``: the parameter tree (``Leaf``), with the repeated layers
    stacked on a leading axis under ``stack_key``; ``vocab``: the number of
    token ids.
    ``embed(p, tok) -> x``; ``layer(p_i, x, ar) -> x``;
    ``head(p, x, ar) -> logits``; each on one sequence, in float32.
    """
    specs: dict
    stack_key: tuple
    n_layers: int
    vocab: int
    embed: object
    layer: object
    head: object


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path, value):
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _set(tree[path[0]], path[1:], value)
    return out


class _Steps:
    """The jitted pieces of one reference step, compiled once per run."""

    def __init__(self, model: Model, ar: Arith):
        def up(t):
            return jax.tree.map(lambda a: a.astype(F32), t)

        def head_loss(p, x, lab, msk):
            nll, z = xent_sums(model.head(p, x, ar), lab, msk)
            return nll + Z_LOSS * z

        # every gradient is taken with respect to a float32 copy of the
        # stored weights, so it is float32 whatever the stored dtype
        def head_vjp(p, x, lab, msk, scale):
            loss, (gp, gx) = jax.value_and_grad(head_loss, argnums=(0, 1))(
                up(p), x, lab, msk)
            return loss, jax.tree.map(lambda g: g * scale, gp), gx * scale

        def layer_vjp(p, x, gy):
            _, vjp = jax.vjp(lambda p_, x_: model.layer(p_, x_, ar),
                             up(p), x)
            return vjp(gy)

        def embed_vjp(p, tok, gx):
            _, vjp = jax.vjp(lambda p_: model.embed(p_, tok), up(p))
            return vjp(gx)[0]

        self.embed = jax.jit(lambda p, tok: model.embed(up(p), tok))
        self.head_vjp = jax.jit(head_vjp)
        self.layer_fwd = jax.jit(lambda p, x: model.layer(up(p), x, ar))
        self.layer_vjp = jax.jit(layer_vjp)
        self.embed_vjp = jax.jit(embed_vjp)
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                           donate_argnums=(0,))


def _split(model: Model, params):
    """(shared params with an empty stack, list of per-layer param trees)."""
    stack = _get(params, model.stack_key)
    layers = [jax.tree.map(lambda a, i=i: a[i], stack)
              for i in range(model.n_layers)]
    return _set(params, model.stack_key, {}), layers


def grads(steps: _Steps, shared, layers, toks, mask, devices):
    """Loss and float32 gradients of the batch mean, one sequence and one
    layer at a time.  ``mask`` (B, S) selects the tokens that count."""
    denom = float(max(mask.sum(), 1.0))
    loss = 0.0
    g_shared, g_layers = None, [None] * len(layers)
    d0 = devices[0]
    for b in range(toks.shape[0]):
        tok = jax.device_put(jnp.asarray(toks[b]), d0)
        msk = jax.device_put(jnp.asarray(mask[b], F32), d0)
        x = steps.embed(shared, tok)
        xs = []
        for i, p in enumerate(layers):
            x = jax.device_put(x, devices[i % len(devices)])
            xs.append(x)
            x = steps.layer_fwd(p, x)
        x = jax.device_put(x, d0)
        l_b, gp, gx = steps.head_vjp(shared, x, tok, msk,
                                     jnp.float32(1.0 / denom))
        loss += float(l_b) / denom
        g_shared = gp if g_shared is None else steps.add(g_shared, gp)
        for i in reversed(range(len(layers))):
            gx = jax.device_put(gx, devices[i % len(devices)])
            gp_i, gx = steps.layer_vjp(layers[i], xs[i], gx)
            g_layers[i] = gp_i if g_layers[i] is None \
                else steps.add(g_layers[i], gp_i)
            xs[i] = None
        gx = jax.device_put(gx, d0)
        g_shared = steps.add(g_shared, steps.embed_vjp(shared, tok, gx))
    return loss, g_shared, g_layers


def _adam_leaf(p, g, m, v, scale, lr, c1, c2):
    g = g * scale
    m = B1 * m + (1 - B1) * g
    v = B2 * v + (1 - B2) * jnp.square(g)
    step = (m / c1) / (jnp.sqrt(v / c2) + EPS)
    p32 = p.astype(F32)
    return (p32 - lr * (step + WD * p32)).astype(p.dtype), m, v


@jax.jit
def sq_norms(tree):
    """Squared norm of every leaf, in float32."""
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a.astype(F32))), tree)


@jax.jit
def sq_change(new, old):
    """Squared norm of every leaf's change from ``old`` to ``new``."""
    return jax.tree.map(lambda a, b: jnp.sum(jnp.square(
        a.astype(F32) - b.astype(F32))), new, old)


_adam = jax.jit(lambda p, g, m, v, s, lr, c1, c2: jax.tree.map(
    lambda *a: _adam_leaf(*a, s, lr, c1, c2), p, g, m, v),
    donate_argnums=(0, 2, 3))


def _unzip3(tree, like):
    flat = jax.tree.leaves(tree, is_leaf=lambda t: isinstance(t, tuple))
    treedef = jax.tree.structure(like)
    return tuple(jax.tree.unflatten(treedef, [t[i] for t in flat])
                 for i in range(3))


def _norms(model: Model, shared_sq, layers_sq) -> dict:
    """{leaf path: norm} from squared norms of the shared part and of each
    layer; a stacked leaf's norm covers all its layers."""
    out = {}
    for path, sq in jax.tree_util.tree_flatten_with_path(shared_sq)[0]:
        out[jax.tree_util.keystr(path)] = float(sq)
    prefix = "".join(f"[{k!r}]" for k in model.stack_key)
    for sq_tree in layers_sq:
        for path, sq in jax.tree_util.tree_flatten_with_path(sq_tree)[0]:
            key = prefix + jax.tree_util.keystr(path)
            out[key] = out.get(key, 0.0) + float(sq)
    return {k: math.sqrt(v) for k, v in out.items()}


def train(model: Model, seed: int, batches, ar: Arith, devices=None,
          keep=None) -> dict:
    """AdamW steps from the seed's weights, one per batch.

    ``batches``: list of (B, S) int32 token arrays.  ``keep``: optional
    (B, S) bool mask of the tokens whose loss counts (default all).

    Returns ``loss`` (per step), ``grad_norm`` ({leaf: norm of the clipped
    first gradient}) and ``update_norm`` ({leaf: norm of the change of the
    parameters over all the steps}).
    """
    devices = devices or jax.devices()[:1]
    steps = _Steps(model, ar)

    def on(i):
        return devices[i % len(devices)]

    with jax.default_matmul_precision("highest"):
        shared, layers = _split(model, jax.device_put(
            init_params(model.specs, seed), devices[0]))
        layers = [jax.device_put(p, on(i)) for i, p in enumerate(layers)]

        def zeros(t):
            return jax.tree.map(lambda a: jnp.zeros_like(a, F32), t)
        m_sh, v_sh = zeros(shared), zeros(shared)
        m_l, v_l = [zeros(p) for p in layers], [zeros(p) for p in layers]
        losses, first = [], None
        for t, toks in enumerate(batches):
            mask = np.ones(toks.shape, np.float32) if keep is None \
                else keep.astype(np.float32)
            loss, g_sh, g_l = grads(steps, shared, layers, toks, mask,
                                    devices)
            losses.append(loss)
            sq_sh, sq_l = sq_norms(g_sh), [sq_norms(g) for g in g_l]
            gnorm = math.sqrt(sum(float(x) for t_ in [sq_sh, *sq_l]
                                  for x in jax.tree.leaves(t_)))
            scale = min(1.0, CLIP / max(gnorm, 1e-9))
            if t == 0:
                first = {k: v * scale
                         for k, v in _norms(model, sq_sh, sq_l).items()}
            args = (jnp.float32(scale), jnp.float32(lr_at(t)),
                    jnp.float32(1 - B1 ** (t + 1)),
                    jnp.float32(1 - B2 ** (t + 1)))
            shared, m_sh, v_sh = _unzip3(
                _adam(shared, g_sh, m_sh, v_sh, *args), shared)
            for i in range(len(layers)):
                layers[i], m_l[i], v_l[i] = _unzip3(_adam(
                    layers[i], g_l[i], m_l[i], v_l[i],
                    *jax.device_put(args, on(i))), layers[i])
            del g_sh, g_l
        del m_sh, v_sh, m_l, v_l
        # the starting weights again, made anew rather than kept throughout
        start_sh, start_l = _split(model, jax.device_put(
            init_params(model.specs, seed), devices[0]))
        change = _norms(model, sq_change(shared, start_sh),
                        [sq_change(p, jax.device_put(s, on(i)))
                         for i, (p, s) in enumerate(zip(layers, start_l,
                                                        strict=True))])
        return {"loss": losses, "grad_norm": first, "update_norm": change}
