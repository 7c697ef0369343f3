"""Paper §V-B end-to-end: NSGA-II activation-checkpointing search on the
MONET cost model, then apply the chosen keep-set to a REAL JAX training
step as a `jax.checkpoint` policy (the beyond-paper integration).

    PYTHONPATH=src python examples/checkpointing_ga.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.core import (build_training_graph, edge_tpu, ga_checkpointing,
                        gpt2_graph, keepset_to_policy)
from repro.core.remat_policy import family_of


def main():
    # 1. search on the simulator (small GPT-2, the paper's NLP case study)
    g = gpt2_graph(batch=1, seq=128, d_model=256, n_layers=2, n_heads=4,
                   vocab=2048)
    tg = build_training_graph(g, "adam")
    hda = edge_tpu()
    res = ga_checkpointing(tg, hda, pop_size=16, generations=8, seed=0)

    print(f"baseline: {res.baseline.act_bytes / 1e6:.2f} MB activations, "
          f"latency {res.baseline.latency:.4g}")
    print(f"Pareto front ({len(res.pareto)} points):")
    for s in res.pareto:
        print(f"  {s.act_bytes / 1e6:6.2f} MB  "
              f"lat ×{s.latency / res.baseline.latency:.3f}  "
              f"E ×{s.energy / res.baseline.energy:.3f}")

    # 2. pick the most memory-frugal point within 10% latency
    ok = [s for s in res.pareto
          if s.latency <= 1.1 * res.baseline.latency]
    chosen = min(ok or res.pareto, key=lambda s: s.act_bytes)
    fams = sorted({f for f in map(family_of, chosen.keep) if f})
    print(f"\nchosen keep-set -> activation families: {fams}")

    # 3. turn it into a jax.checkpoint policy on a real block
    policy = keepset_to_policy(chosen.keep)

    def block(w, x):
        h = checkpoint_name(jnp.tanh(x @ w["w1"]), "mlp_hidden")
        o = checkpoint_name(h @ w["w2"], "attn_out")
        return o.sum()

    w = {"w1": jnp.ones((64, 64)), "w2": jnp.ones((64, 64))}
    x = jnp.ones((8, 64))
    f = jax.checkpoint(block, policy=policy)
    loss, grads = jax.value_and_grad(lambda w: f(w, x))(w)
    print(f"real JAX step under the MONET-chosen policy: loss={loss:.1f}, "
          f"grad norm={jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads))):.1f}")
    print("(the production stack consumes the same policy via "
          "ModelConfig.remat = 'save:<families>')")


if __name__ == "__main__":
    main()
