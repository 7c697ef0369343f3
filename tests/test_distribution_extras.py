"""Tests for the perf-iteration features: SP residuals, fusion repair,
optimizer-variant cells."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import smoke_config
from repro.core.fusion import repair_partition
from repro.core.graph import Node, WorkloadGraph
from repro.core.scheduling import quotient_dag
from repro.distributed.sharding import use_mesh
from repro.models import init_params, logits_fn


def mesh_1x1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def test_seq_sharded_acts_same_logits():
    from dataclasses import replace
    cfg = smoke_config("phi3-medium-14b")
    cfg_sp = replace(cfg, seq_sharded_acts=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    base, _ = logits_fn(params, cfg, toks)
    with use_mesh(mesh_1x1()):
        sp, _ = jax.jit(lambda p, t: logits_fn(p, cfg_sp, t))(params, toks)
    np.testing.assert_allclose(np.asarray(base, np.float32),
                               np.asarray(sp, np.float32), atol=1e-2)


def test_repair_partition_breaks_mutual_cycle():
    """A = {x, w}, B = {y, z} with x→y and z→w: both convex, quotient has a
    2-cycle; repair must break it."""
    g = WorkloadGraph("diamond")
    for t in "abcd":
        g.tensor(t, (4,))
    g.tensor("in1", (4,), is_input=True)
    g.tensor("in2", (4,), is_input=True)
    g.add_node(Node("x", "elementwise", "fwd", dict(N=4), ["in1"], ["a"], 4))
    g.add_node(Node("y", "elementwise", "fwd", dict(N=4), ["a"], ["b"], 4))
    g.add_node(Node("z", "elementwise", "fwd", dict(N=4), ["in2"], ["c"], 4))
    g.add_node(Node("w", "elementwise", "fwd", dict(N=4), ["c"], ["d"], 4))
    bad = [("x", "w"), ("y", "z")]
    fixed = repair_partition(g, bad)
    quotient_dag(g, fixed)  # must not raise
    assert sorted(n for sg in fixed for n in sg) == ["w", "x", "y", "z"]


def test_repair_keeps_acyclic_partition():
    g = WorkloadGraph("chain")
    g.tensor("i", (4,), is_input=True)
    prev = "i"
    for k in range(4):
        g.tensor(f"t{k}", (4,))
        g.add_node(Node(f"n{k}", "elementwise", "fwd", dict(N=4), [prev],
                        [f"t{k}"], 4))
        prev = f"t{k}"
    part = [("n0", "n1"), ("n2", "n3")]
    assert repair_partition(g, part) == [("n0", "n1"), ("n2", "n3")]


def test_cell_optimizer_variant():
    """Adafactor cells produce (much) smaller optimizer state trees."""
    from repro.models.transformer import abstract_params
    from repro.optim.optimizers import make_optimizer
    cfg = smoke_config("phi3-medium-14b")
    ap = abstract_params(cfg)
    adam = jax.eval_shape(make_optimizer("adamw").init, ap)
    af = jax.eval_shape(make_optimizer("adafactor").init, ap)
    size = lambda t: sum(np.prod(l.shape) * l.dtype.itemsize
                         for l in jax.tree.leaves(t))
    assert size(af) < 0.25 * size(adam)
