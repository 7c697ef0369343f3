"""Plain float32 reference of minicpm3-4b as the benchmark runs it, and its
model FLOPs per token.  Sizes come from ``minicpm3-4b.json`` beside this
file.

Each decoder layer: RMSNorm -> multi-head latent attention (MLA) -> residual,
RMSNorm -> SwiGLU MLP -> residual.  MLA, as in DeepSeek-V2 and MiniCPM3:

    c_q  = norm(x W_dq)              q = c_q W_uq = [q_nope | q_rope]
    c_kv = norm(x W_dkv)             k_rope = rope(x W_kr), one head shared
    k_nope = c_kv W_un               v = c_kv W_uv
    o = softmax((q_nope k_nope^T + rope(q_rope) k_rope^T) / sqrt(d_qk)) v

Departures from the published model, as the program has them (listed in
the JSON under ``departures``): the embedding is scaled by sqrt(hidden_size)
and not by scale_emb, the residual branches are not scaled by scale_depth,
the logits are not divided by hidden_size / dim_model_base, RoPE has no
long-context scaling, and each RMSNorm gain is (1 + w) with w starting at 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import plain
from chipbench.plain import Leaf


def _sizes(conf: dict) -> dict:
    return {k: conf[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "intermediate_size", "vocab_size", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rms_norm_eps", "rope_theta")}


def model(conf: dict) -> plain.Model:
    c = _sizes(conf)
    D, L, H = c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"]
    F, V = c["intermediate_size"], c["vocab_size"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    dqk = dn + dr
    bf, f32 = conf["param_dtype"], "float32"

    specs = {
        "embed": {"table": Leaf((V, D), bf, "small")},
        "final_norm": {"scale": Leaf((D,), f32, "zeros")},
        "head": {"w": Leaf((D, V), bf, "small")},
        "scan": {"0": {
            "attn": {
                "wdq": Leaf((L, D, rq), bf),
                "q_ln": Leaf((L, rq), f32, "zeros"),
                "wuq": Leaf((L, rq, H * dqk), bf),
                "wdkv": Leaf((L, D, rkv), bf),
                "kv_ln": Leaf((L, rkv), f32, "zeros"),
                "wkr": Leaf((L, D, dr), bf),
                "wun": Leaf((L, rkv, H * dn), bf),
                "wuv": Leaf((L, rkv, H * dv), bf),
                "wo": Leaf((L, H * dv, D), bf),
            },
            "ln1": {"scale": Leaf((L, D), f32, "zeros")},
            "ln2": {"scale": Leaf((L, D), f32, "zeros")},
            "mlp": {"wi": Leaf((L, D, F), bf), "wg": Leaf((L, D, F), bf),
                    "wo": Leaf((L, F, D), bf)},
        }},
    }

    def embed(p, tok):
        return p["embed"]["table"][tok] * math.sqrt(D)

    def layer(p, x, ar):
        S = x.shape[0]
        pos = jnp.arange(S)
        a = p["attn"]
        h = plain.rmsnorm(x, p["ln1"]["scale"], eps)
        cq = plain.rmsnorm(ar.ein("sd,dr->sr", h, a["wdq"]), a["q_ln"], eps)
        q = ar.ein("sr,rf->sf", cq, a["wuq"]).reshape(S, H, dqk)
        q_nope, q_rope = q[..., :dn], plain.rope(q[..., dn:], pos, theta)
        ckv = plain.rmsnorm(ar.ein("sd,dr->sr", h, a["wdkv"]), a["kv_ln"],
                            eps)
        k_rope = plain.rope(ar.ein("sd,dr->sr", h, a["wkr"])[:, None, :],
                            pos, theta)
        k_nope = ar.ein("sr,rf->sf", ckv, a["wun"]).reshape(S, H, dn)
        v = ar.ein("sr,rf->sf", ckv, a["wuv"]).reshape(S, H, dv)
        scores = (ar.ein("shd,thd->hst", q_nope, k_nope)
                  + ar.ein("shd,td->hst", q_rope, k_rope[:, 0])
                  ) / math.sqrt(dqk)
        causal = jnp.tril(jnp.ones((S, S), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        ctx = ar.ein("hst,thd->shd", probs, v).reshape(S, H * dv)
        x = x + ar.ein("sf,fd->sd", ctx, a["wo"])
        m = p["mlp"]
        h2 = plain.rmsnorm(x, p["ln2"]["scale"], eps)
        u = jax.nn.silu(ar.ein("sd,df->sf", h2, m["wg"])) \
            * ar.ein("sd,df->sf", h2, m["wi"])
        return x + ar.ein("sf,fd->sd", u, m["wo"])

    def head(p, x, ar):
        x = plain.rmsnorm(x, p["final_norm"]["scale"], eps)
        return ar.ein("sd,dv->sv", x, p["head"]["w"])

    return plain.Model(specs, ("scan", "0"), L, V, embed, layer, head)


def program_fields(conf: dict) -> dict:
    """The program's ModelConfig fields that this file fixes."""
    c = _sizes(conf)
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
        "mlp": "swiglu", "pattern": ["mla"],
        "mla": {"q_lora_rank": c["q_lora_rank"],
                "kv_lora_rank": c["kv_lora_rank"],
                "qk_nope_dim": c["qk_nope_head_dim"],
                "qk_rope_dim": c["qk_rope_head_dim"],
                "v_head_dim": c["v_head_dim"]},
        "norm_eps": c["rms_norm_eps"], "rope_theta": c["rope_theta"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "param_dtype": conf["param_dtype"],
        "compute_dtype": conf["compute_dtype"],
        "state_dtype": conf["optimizer_state_dtype"],
    }


def flops_per_token(conf: dict, seq_len: int, causal: bool = True) -> float:
    """Model FLOPs of one trained token (forward + backward, no recompute):
    6 x the parameters of every matrix product (the embedding gather
    excluded, the unembedding included), plus 3 x the attention score and
    context products, over the causal half of the keys (all keys with
    ``causal=False``)."""
    c = _sizes(conf)
    D, L, H = c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"]
    F, V = c["intermediate_size"], c["vocab_size"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    mla = (D * rq + rq * H * (dn + dr) + D * rkv + D * dr
           + rkv * H * dn + rkv * H * dv + H * dv * D)
    per_layer = mla + 3 * D * F
    keys = (seq_len + 1) / 2 if causal else seq_len
    attn = 3 * 2 * H * (dn + dr + dv) * keys
    return 6 * (L * per_layer + D * V) + L * attn
