"""The composable decoder-only model: dense / GQA / MLA / MoE / SSM / hybrid,
assembled from a ModelConfig.

The layer stack is ``jax.lax.scan`` over the smallest repeating block pattern
(`cfg.scan_period()`), with stacked parameters — compact HLO even at 340 B —
plus an unrolled remainder for patterns that don't divide n_layers.
Activation checkpointing wraps the scanned period body with the policy
chosen by `cfg.remat` (optionally produced by MONET's GA — see
core.remat_policy).
"""

from __future__ import annotations


import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp

from ..core.remat_policy import resolve_remat
from ..distributed.sharding import shard
from .attention import (attn_decode_step, attn_specs, gqa_attention,
                        mla_attention, mla_decode_step, mla_specs)
from .layers import (PSpec, abstract, axes_tree, materialize, mlp_apply,
                     mlp_specs, rmsnorm, rmsnorm_spec, stack_specs)
from .moe import moe_apply, moe_specs
from .ssm import ssd_apply, ssd_decode_step, ssm_specs


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def block_specs(cfg, spec) -> dict:
    out = {"ln1": rmsnorm_spec(cfg.d_model)}
    if spec.mixer in ("attn", "local"):
        out["attn"] = attn_specs(cfg)
    elif spec.mixer == "mla":
        out["attn"] = mla_specs(cfg)
    elif spec.mixer == "mamba":
        out["mixer"] = ssm_specs(cfg)
    else:
        raise ValueError(f"unknown mixer {spec.mixer!r}")
    if spec.moe:
        out["ln2"] = rmsnorm_spec(cfg.d_model)
        out["moe"] = moe_specs(cfg)
    elif cfg.mlp != "none":
        out["ln2"] = rmsnorm_spec(cfg.d_model)
        out["mlp"] = mlp_specs(cfg)
    return out


def param_specs(cfg) -> dict:
    specs = cfg.layer_specs()
    period = cfg.scan_period()
    n_full = cfg.n_layers // period
    rem = cfg.n_layers - n_full * period

    tree: dict = {}
    if cfg.input_mode == "tokens":
        tree["embed"] = {"table": PSpec((cfg.vocab, cfg.d_model),
                                        ("vocab", "embed"), cfg.param_dtype,
                                        "small")}
    tree["scan"] = {str(i): stack_specs(block_specs(cfg, specs[i]), n_full)
                    for i in range(period)}
    tree["rem"] = {str(j): block_specs(cfg, specs[n_full * period + j])
                   for j in range(rem)}
    tree["final_norm"] = rmsnorm_spec(cfg.d_model)
    if not cfg.tie_embeddings:
        tree["head"] = {"w": PSpec((cfg.d_model, cfg.vocab),
                                   ("embed", "vocab"), cfg.param_dtype,
                                   "small")}
    return tree


def init_params(cfg, rng: jax.Array):
    return materialize(param_specs(cfg), rng)


def abstract_params(cfg):
    return abstract(param_specs(cfg))


def param_axes(cfg):
    return axes_tree(param_specs(cfg))


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _apply_layer(prm, x, cfg, spec, positions):
    # named scopes: a profiler trace and the HLO's op_name carry them
    with jax.named_scope("mixer"):
        h = rmsnorm(x, prm["ln1"]["scale"], cfg.norm_eps)
        h = checkpoint_name(h, "attn_in")
        if spec.mixer == "attn":
            with jax.named_scope("attn"):
                mix = gqa_attention(prm["attn"], h, cfg, positions,
                                    window=None)
        elif spec.mixer == "local":
            with jax.named_scope("attn"):
                mix = gqa_attention(prm["attn"], h, cfg, positions,
                                    window=cfg.window)
        elif spec.mixer == "mla":
            with jax.named_scope("mla"):
                mix = mla_attention(prm["attn"], h, cfg, positions)
        else:
            with jax.named_scope("ssd"):
                mix = ssd_apply(prm["mixer"], h, cfg)
        x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if spec.moe:
        with jax.named_scope("moe"):
            h2 = rmsnorm(x, prm["ln2"]["scale"], cfg.norm_eps)
            y, aux = moe_apply(prm["moe"], h2, cfg)
            x = x + y
    elif cfg.mlp != "none":
        with jax.named_scope("mlp"):
            h2 = rmsnorm(x, prm["ln2"]["scale"], cfg.norm_eps)
            x = x + mlp_apply(prm["mlp"], h2, cfg)
    x = checkpoint_name(x, "block_out")
    seq_ax = "seq_sp" if cfg.seq_sharded_acts else "seq"
    return shard(x, "batch", seq_ax, "embed_act"), aux


def forward_hidden(params, cfg, inputs, positions=None):
    """inputs: tokens (B,S) int32, or embeddings (B,S,D) for stub-frontend
    archs.  Returns (hidden (B,S,D), aux_loss)."""
    specs = cfg.layer_specs()
    period = cfg.scan_period()
    n_full = cfg.n_layers // period

    with jax.named_scope("embed"):
        if cfg.input_mode == "tokens":
            x = params["embed"]["table"][inputs]
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        else:
            x = inputs.astype(cfg.compute_dtype)
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = shard(x, "batch", "seq_sp" if cfg.seq_sharded_acts else "seq",
              "embed_act")

    def period_body(x, per_params):
        aux = jnp.zeros((), jnp.float32)
        for i in range(period):
            x, a = _apply_layer(per_params[str(i)], x, cfg, specs[i],
                                positions)
            aux = aux + a
        return x, aux

    use_remat, policy = resolve_remat(cfg.remat)
    if use_remat:
        period_body = jax.checkpoint(period_body, policy=policy,
                                     prevent_cse=False)

    def scan_body(carry, per_params):
        x, aux = carry
        x, a = period_body(x, per_params)
        return (x, aux + a), None

    if n_full > 0:
        (x, aux), _ = jax.lax.scan(scan_body,
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["scan"],
                                   unroll=min(cfg.scan_unroll, n_full))
    else:
        aux = jnp.zeros((), jnp.float32)
    for j, prm in sorted(params.get("rem", {}).items(), key=lambda kv: int(kv[0])):
        spec = specs[n_full * period + int(j)]
        x, a = _apply_layer(prm, x, cfg, spec, positions)
        aux = aux + a

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return x, aux


def unembed_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["head"]["w"]


def logits_fn(params, cfg, inputs):
    h, aux = forward_hidden(params, cfg, inputs)
    logits = h @ unembed_weight(params, cfg)
    return shard(logits, "batch", "seq", "vocab"), aux


# ---------------------------------------------------------------------------
# KV / SSM caches + decode
# ---------------------------------------------------------------------------


def _cache_entry_specs(cfg, spec, batch: int, max_seq: int,
                       kv_seq_axis) -> dict:
    hd, Kv = cfg.head_dim_, cfg.n_kv_heads
    if spec.mixer == "attn":
        shp = (batch, max_seq, Kv, hd)
        axes = ("batch", kv_seq_axis, "kv_heads", None)
        return {"k": PSpec(shp, axes, cfg.compute_dtype, "zeros"),
                "v": PSpec(shp, axes, cfg.compute_dtype, "zeros")}
    if spec.mixer == "local":
        w = min(cfg.window, max_seq)
        shp = (batch, w, Kv, hd)
        axes = ("batch", kv_seq_axis, "kv_heads", None)
        return {"k": PSpec(shp, axes, cfg.compute_dtype, "zeros"),
                "v": PSpec(shp, axes, cfg.compute_dtype, "zeros")}
    if spec.mixer == "mla":
        m = cfg.mla
        return {"ckv": PSpec((batch, max_seq, m.kv_lora_rank),
                             ("batch", kv_seq_axis, None),
                             cfg.compute_dtype, "zeros"),
                "kr": PSpec((batch, max_seq, m.qk_rope_dim),
                            ("batch", kv_seq_axis, None),
                            cfg.compute_dtype, "zeros")}
    if spec.mixer == "mamba":
        s = cfg.ssm
        ch = cfg.d_inner + 2 * s.n_groups * s.d_state
        return {"conv": PSpec((batch, s.conv_width - 1, ch),
                              ("batch", None, None), "float32", "zeros"),
                "state": PSpec((batch, cfg.ssm_heads, s.headdim, s.d_state),
                               ("batch", "ffn", None, None), "float32",
                               "zeros")}
    raise ValueError(spec.mixer)


def cache_specs(cfg, batch: int, max_seq: int, shard_kv_seq: bool = False
                ) -> dict:
    specs = cfg.layer_specs()
    period = cfg.scan_period()
    n_full = cfg.n_layers // period
    rem = cfg.n_layers - n_full * period
    kv_ax = "kv_seq"   # cache seq dim shards over 'model' (or the full
                       # mesh under the long_500k rules override)
    del shard_kv_seq
    tree = {
        "scan": {str(i): stack_specs(
            _cache_entry_specs(cfg, specs[i], batch, max_seq, kv_ax), n_full)
            for i in range(period)},
        "rem": {str(j): _cache_entry_specs(
            cfg, specs[n_full * period + j], batch, max_seq, kv_ax)
            for j in range(rem)},
    }
    return tree


def init_cache(cfg, batch: int, max_seq: int, shard_kv_seq: bool = False):
    return materialize(cache_specs(cfg, batch, max_seq, shard_kv_seq),
                       jax.random.PRNGKey(0))


def cache_axes(cfg, batch: int, max_seq: int, shard_kv_seq: bool = False):
    return axes_tree(cache_specs(cfg, batch, max_seq, shard_kv_seq))


def _decode_layer(prm, cache, x, pos, cfg, spec):
    h = rmsnorm(x, prm["ln1"]["scale"], cfg.norm_eps)
    if spec.mixer == "attn":
        mix, k, v = attn_decode_step(prm["attn"], h, cache["k"], cache["v"],
                                     pos, cfg, window=None)
        cache = {"k": k, "v": v}
    elif spec.mixer == "local":
        mix, k, v = attn_decode_step(prm["attn"], h, cache["k"], cache["v"],
                                     pos, cfg, window=cfg.window)
        cache = {"k": k, "v": v}
    elif spec.mixer == "mla":
        mix, ckv, kr = mla_decode_step(prm["attn"], h, cache["ckv"],
                                       cache["kr"], pos, cfg)
        cache = {"ckv": ckv, "kr": kr}
    else:
        mix, conv, state = ssd_decode_step(prm["mixer"], h, cache["conv"],
                                           cache["state"], cfg)
        cache = {"conv": conv, "state": state}
    x = x + mix
    if spec.moe:
        h2 = rmsnorm(x, prm["ln2"]["scale"], cfg.norm_eps)
        y, _ = moe_apply(prm["moe"], h2, cfg)
        x = x + y
    elif cfg.mlp != "none":
        h2 = rmsnorm(x, prm["ln2"]["scale"], cfg.norm_eps)
        x = x + mlp_apply(prm["mlp"], h2, cfg)
    return x, cache


def decode_step(params, cache, cfg, inputs, pos):
    """One-token decode.  inputs: (B,1) tokens or (B,1,D) embeddings;
    pos: scalar int32 (current cache fill).  Returns (logits, new_cache)."""
    specs = cfg.layer_specs()
    period = cfg.scan_period()
    n_full = cfg.n_layers // period

    if cfg.input_mode == "tokens":
        x = params["embed"]["table"][inputs]
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    else:
        x = inputs.astype(cfg.compute_dtype)

    def scan_body(x, inp):
        per_params, per_cache = inp
        new_cache = {}
        for i in range(period):
            x, new_cache[str(i)] = _decode_layer(
                per_params[str(i)], per_cache[str(i)], x, pos, cfg, specs[i])
        return x, new_cache

    new_cache = {"scan": cache["scan"], "rem": {}}
    if n_full > 0:
        x, new_cache["scan"] = jax.lax.scan(
            scan_body, x, (params["scan"], cache["scan"]),
            unroll=min(cfg.scan_unroll, n_full))
    for j, prm in sorted(params.get("rem", {}).items(),
                         key=lambda kv: int(kv[0])):
        spec = specs[n_full * period + int(j)]
        x, new_cache["rem"][j] = _decode_layer(prm, cache["rem"][j], x, pos,
                                               cfg, spec)

    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = x @ unembed_weight(params, cfg)
    return shard(logits, "batch", "seq", "vocab"), new_cache
