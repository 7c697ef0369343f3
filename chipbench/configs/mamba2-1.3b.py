"""Plain float32 reference of mamba2-1.3b as the benchmark runs it, and its
model FLOPs per token.  Sizes come from ``mamba2-1.3b.json`` beside this
file.

Each layer: RMSNorm -> Mamba-2 mixer -> residual.  The mixer (arXiv
2405.21060, section 7 and listing 1):

    z = x W_z    u = silu(conv(x W_x))    [B | C] = silu(conv(x W_bc))
    dt = softplus(x W_dt + dt_bias)       A = -exp(A_log)
    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s u_s + D u_t
    out = norm(y * silu(z)) W_o

The scan is the paper's chunked "minimal SSD": quadratic within each chunk,
a segment-sum recurrence across chunks.  The depthwise causal convolutions
have no bias.

Departures from the published model, as the program has them (listed in
the JSON under ``departures``): the embedding and the unembedding are two
matrices, the embedding is scaled by sqrt(d_model), the convolutions have no
bias, A_log starts at 0 (A = -1), and each RMSNorm gain is (1 + w) with w
starting at 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import plain
from chipbench.plain import Leaf


def _sizes(conf: dict) -> dict:
    c = {k: conf[k] for k in ("d_model", "n_layer", "vocab_size",
                              "rms_norm_eps", "tie_embeddings")}
    c.update({k: conf["ssm_cfg"][k] for k in (
        "d_state", "d_conv", "expand", "headdim", "ngroups", "chunk_size")})
    return c


def _segsum(a):
    """(..., T) -> (..., T, T): sum of a over (s, t], -inf above the
    diagonal."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], (*a.shape, T))
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), x, -jnp.inf)


def ssd(X, A, B, C, chunk, ar):
    """X: (S, h, p) = dt * u; A: (S, h) = dt * A; B, C: (S, h, n)."""
    S, h, p = X.shape
    c = S // chunk
    X, A, B, C = (t.reshape(c, chunk, *t.shape[1:]) for t in (X, A, B, C))
    A = A.transpose(2, 0, 1)                                  # (h, c, l)
    A_cum = jnp.cumsum(A, axis=-1)
    L = jnp.exp(_segsum(A))                                   # (h, c, l, s)
    CB = ar.ein("clhn,cshn->hcls", C, B)
    Y_diag = ar.ein("hcls,cshp->clhp", CB * L, X)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                  # (h, c, l)
    states = ar.ein("clhn,clhp->chpn",
                    B * decay.transpose(1, 2, 0)[..., None], X)
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], 0)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cum[..., -1], ((0, 0), (1, 0)))))
    states = jnp.einsum("hzc,chpn->zhpn", decay_chunk, states,
                        precision=plain.HIGHEST)[:-1]
    Y_off = ar.ein("clhn,chpn->clhp",
                   C * jnp.exp(A_cum).transpose(1, 2, 0)[..., None], states)
    return (Y_diag + Y_off).reshape(S, h, p)


def model(conf: dict) -> plain.Model:
    c = _sizes(conf)
    D, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    N, cw, hp, G = c["d_state"], c["d_conv"], c["headdim"], c["ngroups"]
    di = c["expand"] * D
    nh, eps, Q = di // hp, c["rms_norm_eps"], c["chunk_size"]
    bf, f32 = conf["param_dtype"], "float32"

    specs = {
        "embed": {"table": Leaf((V, D), bf, "small")},
        "final_norm": {"scale": Leaf((D,), f32, "zeros")},
        "scan": {"0": {
            "ln1": {"scale": Leaf((L, D), f32, "zeros")},
            "mixer": {
                "wz": Leaf((L, D, di), bf),
                "wx": Leaf((L, D, di), bf),
                "wbc": Leaf((L, D, 2 * G * N), bf),
                "wdt": Leaf((L, D, nh), bf),
                "conv_x": Leaf((L, cw, di), f32),
                "conv_bc": Leaf((L, cw, 2 * G * N), f32),
                "A_log": Leaf((L, nh), f32, "zeros"),
                "dt_bias": Leaf((L, nh), f32, "zeros"),
                "D": Leaf((L, nh), f32, "ones"),
                "norm": Leaf((L, di), f32, "zeros"),
                "wo": Leaf((L, di, D), bf),
            },
        }},
    }
    if not c["tie_embeddings"]:
        specs["head"] = {"w": Leaf((D, V), bf, "small")}

    def conv(u, w):
        S = u.shape[0]
        up = jnp.pad(u, ((cw - 1, 0), (0, 0)))
        return sum(w[k] * up[k:k + S] for k in range(cw))

    def embed(p, tok):
        return p["embed"]["table"][tok] * math.sqrt(D)

    def layer(p, x, ar):
        S = x.shape[0]
        m = p["mixer"]
        h = plain.rmsnorm(x, p["ln1"]["scale"], eps)
        z = ar.ein("sd,df->sf", h, m["wz"])
        u = jax.nn.silu(conv(ar.ein("sd,df->sf", h, m["wx"]), m["conv_x"]))
        bc = jax.nn.silu(conv(ar.ein("sd,df->sf", h, m["wbc"]),
                              m["conv_bc"]))
        Bm = jnp.repeat(bc[:, :G * N].reshape(S, G, N), nh // G, axis=1)
        Cm = jnp.repeat(bc[:, G * N:].reshape(S, G, N), nh // G, axis=1)
        dt = jax.nn.softplus(ar.ein("sd,dh->sh", h, m["wdt"]) + m["dt_bias"])
        A = -jnp.exp(m["A_log"])
        uh = u.reshape(S, nh, hp)
        y = ssd(uh * dt[..., None], dt * A, Bm, Cm, min(Q, S), ar)
        y = (y + uh * m["D"][:, None]).reshape(S, di)
        y = plain.rmsnorm(y * jax.nn.silu(z), m["norm"], eps)
        return x + ar.ein("sf,fd->sd", y, m["wo"])

    def head(p, x, ar):
        x = plain.rmsnorm(x, p["final_norm"]["scale"], eps)
        w = p["embed"]["table"].T if c["tie_embeddings"] else p["head"]["w"]
        return ar.ein("sd,dv->sv", x, w)

    return plain.Model(specs, ("scan", "0"), L, V, embed, layer, head)


def program_fields(conf: dict) -> dict:
    """The program's ModelConfig fields that this file fixes."""
    c = _sizes(conf)
    return {
        "n_layers": c["n_layer"], "d_model": c["d_model"],
        "vocab": c["vocab_size"], "mlp": "none", "pattern": ["mamba"],
        "ssm": {"d_state": c["d_state"], "headdim": c["headdim"],
                "expand": c["expand"], "conv_width": c["d_conv"],
                "n_groups": c["ngroups"], "chunk": c["chunk_size"]},
        "norm_eps": c["rms_norm_eps"],
        "tie_embeddings": c["tie_embeddings"],
        "param_dtype": conf["param_dtype"],
        "compute_dtype": conf["compute_dtype"],
        "state_dtype": conf["optimizer_state_dtype"],
    }


def flops_per_token(conf: dict, seq_len: int, causal: bool = True) -> float:
    """Model FLOPs of one trained token (forward + backward, no recompute):
    6 x the parameters of every matrix product (the embedding gather
    excluded, the unembedding included), plus 3 x the SSD products: C.B and
    its product with the inputs over the causal half of each chunk (the whole
    chunk with ``causal=False``), the chunk states, and the state-to-output
    product."""
    c = _sizes(conf)
    D, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    N, hp, G = c["d_state"], c["headdim"], c["ngroups"]
    di = c["expand"] * D
    nh = di // hp
    Q = min(c["chunk_size"], seq_len)
    per_layer = D * (2 * di + 2 * G * N + nh) + di * D
    keys = (Q + 1) / 2 if causal else Q
    ssd_fwd = nh * (2 * N * keys + 2 * hp * keys + 2 * N * hp + 2 * N * hp)
    return 6 * (L * per_layer + D * V) + 3 * L * ssd_fwd
