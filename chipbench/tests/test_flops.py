"""Each configuration's FLOPs-per-token function against the program's own
step at a smoke size, with every scan unrolled and no recompute: it equals
the matrix-product FLOPs of the step's jaxpr exactly (with all keys counted,
as the program computes the masked half too), and XLA's cost analysis of
the compiled step counts those and some elementwise work on top."""

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.smoke import no_remat, smoke_cell

B, S = 2, 64


def dot_flops(jaxpr, mult=1) -> int:
    total = 0
    for eq in jaxpr.eqns:
        if eq.primitive.name == "dot_general":
            (lc, _), _ = eq.params["dimension_numbers"]
            a = eq.invars[0].aval.shape
            total += mult * 2 * math.prod(eq.outvars[0].aval.shape) \
                * math.prod(a[i] for i in lc)
        times = eq.params.get("length", 1) if eq.primitive.name == "scan" \
            else 1
        for p in eq.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if hasattr(sub, "consts") and hasattr(sub, "jaxpr"):
                    total += dot_flops(sub.jaxpr, mult * times)
                elif hasattr(sub, "eqns"):
                    total += dot_flops(sub, mult * times)
    return total


@pytest.mark.parametrize("config", ["minicpm3-4b", "mamba2-1.3b"])
def test_flops_per_token_matches_the_step(config):
    from repro.models.transformer import init_params
    from repro.training.loss import lm_loss
    cell = smoke_cell(config)
    cfg = replace(no_remat(cell), scan_unroll=64)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    step = jax.value_and_grad(lambda p, t: lm_loss(p, cfg, t, t)[0])
    dots = dot_flops(jax.make_jaxpr(step)(params, toks).jaxpr)
    full = cell.model.flops_per_token(cell.config, S, causal=False) * B * S
    causal = cell.model.flops_per_token(cell.config, S) * B * S
    assert dots == full
    assert causal < full
    xla = jax.jit(step).lower(params, toks).compile().cost_analysis()["flops"]
    assert full <= xla <= 2 * full
