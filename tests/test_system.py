"""End-to-end behaviour tests for the paper's system: the full MONET
pipeline (graph → training transform → HDA cost → fusion → AC-GA →
jax.checkpoint policy) plus the claims the paper makes about it."""

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
import numpy as np

from repro.core import (FusionConfig, build_training_graph, edge_tpu,
                        evaluate_checkpointing, fusemax, ga_checkpointing,
                        gpt2_graph, keepset_to_policy, layer_by_layer,
                        manual_fusion, mlp_graph, resnet18_graph, schedule,
                        solve_fusion, activation_set)


def test_paper_pipeline_end_to_end():
    """ResNet-18 (CIFAR) on the baseline Edge TPU: build training graph,
    fuse, checkpoint, cost — the full §III workflow."""
    hda = edge_tpu()
    fwd = resnet18_graph(1, 32)
    tg = build_training_graph(fwd, "adam")

    inf = schedule(fwd, hda, manual_fusion(fwd))
    part = solve_fusion(tg.graph, hda, FusionConfig(max_len=6,
                                                    time_limit_s=5))
    tr = schedule(tg.graph, hda, part)

    # paper Fig. 1: training and inference land in different regimes
    assert tr.latency > 2 * inf.latency
    assert tr.energy > 2 * inf.energy
    assert tr.peak_mem > inf.peak_mem

    # AC: discarding activations trades latency/energy for memory
    acts = activation_set(tg)
    base = evaluate_checkpointing(tg, hda, set(acts))
    none = evaluate_checkpointing(tg, hda, set())
    assert none.act_bytes == 0 < base.act_bytes


def test_inference_vs_training_hardware_ranking_differs():
    """Paper's core DSE claim: conclusions drawn from inference-only
    analysis do not transfer to training."""
    fwd = resnet18_graph(1, 32)
    tg = build_training_graph(fwd, "adam").graph
    configs = [dict(x_pes=2, y_pes=2, simd_units=128, lanes=8),
               dict(x_pes=8, y_pes=8, simd_units=16, lanes=1),
               dict(x_pes=4, y_pes=4, simd_units=64, lanes=4),
               dict(x_pes=1, y_pes=8, simd_units=64, lanes=2)]
    inf_lat, tr_lat = [], []
    for c in configs:
        hda = edge_tpu(**c)
        inf_lat.append(schedule(fwd, hda).latency)
        tr_lat.append(schedule(tg, hda).latency)
    # the train/inference latency ratio is config-dependent (structurally
    # different landscapes, Fig. 1) — not a constant scaling
    ratios = [t / i for t, i in zip(tr_lat, inf_lat, strict=True)]
    assert max(ratios) / min(ratios) > 1.05


def test_fusion_beats_baselines_on_training_graph():
    """Paper Fig. 10 (extended to training): IP fusion ≤ layer-by-layer."""
    hda = edge_tpu()
    tg = build_training_graph(resnet18_graph(1, 32)).graph
    base = schedule(tg, hda, layer_by_layer(tg))
    fused = schedule(tg, hda,
                     solve_fusion(tg, hda, FusionConfig(max_len=6,
                                                        time_limit_s=8)))
    assert fused.latency < base.latency
    assert fused.energy < base.energy


def test_ga_front_reaches_lower_memory_with_bounded_latency():
    hda = edge_tpu()
    tg = build_training_graph(mlp_graph(batch=32, widths=(256, 256, 256)))
    res = ga_checkpointing(tg, hda, pop_size=12, generations=8, seed=3)
    best_mem = min(s.act_bytes for s in res.pareto)
    assert best_mem < res.baseline.act_bytes
    # and the front contains a solution within 10% latency of baseline
    ok = [s for s in res.pareto
          if s.latency <= 1.10 * res.baseline.latency]
    assert ok


def test_monet_decision_drives_real_jax_step():
    """Beyond-paper integration: an AC keep-set becomes a jax.checkpoint
    policy usable on the real training step (same grads either way)."""
    policy = keepset_to_policy({"l0.fc1.out", "l0.q.out"})
    assert policy is not None

    def block(w, x):
        h = checkpoint_name(jnp.tanh(x @ w), "mlp_hidden")
        return h @ w.T

    w = jnp.ones((16, 16))
    x = jnp.ones((4, 16))

    f_full = jax.checkpoint(
        block, policy=jax.checkpoint_policies.everything_saveable)
    f_pol = jax.checkpoint(block, policy=policy)
    g1 = jax.grad(lambda w: f_full(w, x).sum())(w)
    g2 = jax.grad(lambda w: f_pol(w, x).sum())(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-6)


def test_gpt2_on_fusemax_study():
    """Paper §IV-B: small GPT-2 on FuseMax — homogeneous workload."""
    hda = fusemax()
    g = gpt2_graph(1, 128, 256, 2, 4, 512)
    tg = build_training_graph(g).graph
    inf = schedule(g, hda, manual_fusion(g))
    tr = schedule(tg, hda, manual_fusion(tg))
    assert tr.latency > inf.latency
    assert tr.peak_mem > inf.peak_mem
