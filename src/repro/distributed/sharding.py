"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Physical mesh axes:
  * single-pod:  ('data', 'model')            — 16 × 16 = 256 chips
  * multi-pod:   ('pod', 'data', 'model')     — 2 × 16 × 16 = 512 chips

Logical axes used by models/optimizers map onto physical axes via RULES.
Rules are resolved against the *actual* mesh, silently dropping axes the
mesh does not have (so the same model code runs single- and multi-pod).

Parameter layout (ZeRO/FSDP hybrid):
  * weights:   embed-dim sharded over 'data' (FSDP), ff/heads/vocab over
    'model' (TP); replicated across 'pod' (grads all-reduced over DCN).
  * optimizer states: additionally sharded over 'pod' (ZeRO-across-pods).
  * activations: batch over ('pod','data'), heads/ffn/vocab over 'model'.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> tuple of physical mesh axes (first existing ones are used)
RULES: dict[str, tuple] = {
    "batch": ("pod", "data"),
    "seq": (),                      # replicated by default (no SP)
    "seq_sp": ("model",),           # Megatron-style sequence parallelism for
                                    # the residual stream between blocks
    "kv_seq": ("model",),           # KV-cache seq dim: TP axis by default
                                    # (kv_heads < model size cannot shard);
                                    # long_500k overrides to (pod,data,model)
    "embed": ("data",),             # FSDP shard dim of weights
    "embed_act": (),                # activation d_model dim: replicated
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_ffn": (),
    "zero": ("pod", "data"),        # optimizer-state extra sharding
    "conv": (),
    "state": (),
    None: (),
}


_ctx = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_ctx, "mesh", None)


def current_rules() -> dict:
    return getattr(_ctx, "rules", RULES)


@contextlib.contextmanager
def rules_override(**overrides):
    """Per-launch logical-rule overrides, e.g. long_500k (global_batch=1):
    ``rules_override(batch=(), kv_seq=('pod', 'data'))`` moves the sharding
    from the (size-1) batch dim onto the KV sequence dim."""
    old = current_rules()
    new = dict(old)
    for k, v in overrides.items():
        if k not in RULES:
            raise KeyError(f"unknown logical axis {k!r}")
        new[k] = tuple(v)
    _ctx.rules = new
    try:
        yield
    finally:
        _ctx.rules = old


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    """Activate a mesh for the logical-axis helpers.  All shardings are
    explicit NamedShardings, so no ambient-XLA mesh state is needed."""
    old = current_mesh()
    _ctx.mesh = mesh
    try:
        yield mesh
    finally:
        _ctx.mesh = old


def resolve_axes(logical: str | None, mesh: Mesh) -> tuple:
    rules = current_rules()
    phys = rules.get(logical, ())
    if logical is not None and logical not in rules:
        raise KeyError(f"unknown logical axis {logical!r}")
    present = tuple(a for a in phys if a in mesh.axis_names)
    return present


def pspec(logical_axes, mesh: Mesh | None = None) -> P:
    """logical axes tuple (one entry per tensor dim; None = replicated) →
    PartitionSpec resolved against the mesh.

    A logical axis whose *rule* names several physical axes always resolves
    to a tuple entry (even when only one of them is present on this mesh),
    so specs are mesh-shape-stable; single-axis rules resolve to the bare
    axis name.  Current ``jax.sharding.PartitionSpec`` compares entries
    structurally ('data' != ('data',)), so this distinction is load-bearing.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        return P()
    rules = current_rules()
    used: set = set()
    parts = []
    for ax in logical_axes:
        if ax is not None and ax not in rules:
            raise KeyError(f"unknown logical axis {ax!r}")
        rule = rules.get(ax, ())
        phys = tuple(a for a in rule if a in mesh.axis_names
                     and a not in used)
        used.update(phys)
        if len(phys) == 0:
            parts.append(None)
        elif len(rule) > 1:
            parts.append(tuple(phys))
        else:
            parts.append(phys[0])
    return P(*parts)


def named_sharding(logical_axes, mesh: Mesh | None = None) -> NamedSharding:
    mesh = mesh or current_mesh()
    assert mesh is not None, "no mesh active"
    return NamedSharding(mesh, pspec(logical_axes, mesh))


def prune_pspec(shape: tuple, spec: P, mesh: Mesh) -> P:
    """Drop physical axes that do not divide the dim size (e.g. kv_heads=1
    cannot shard over model=16 — it falls back to replicated)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, part in zip(shape, parts, strict=False):
        if part is None:
            out.append(None)
            continue
        axes = part if isinstance(part, tuple) else (part,)
        keep, size = [], 1
        for a in axes:
            m = int(mesh.shape[a])
            if dim % (size * m) == 0:
                keep.append(a)
                size *= m
        out.append(tuple(keep) if len(keep) > 1 else
                   (keep[0] if keep else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x, *logical_axes):
    """with_sharding_constraint by logical axes; no-op without a mesh;
    axes that don't divide the dim are dropped (replicated)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(f"rank {x.ndim} vs axes {logical_axes}")
    spec = prune_pspec(x.shape, pspec(logical_axes, mesh), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# pytree-of-logical-axes helpers (params / opt-state / batch shardings)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Ax:
    """A leaf annotation: logical axes for one tensor."""
    axes: tuple

    def __iter__(self):
        return iter(self.axes)


def ax(*axes) -> Ax:
    return Ax(tuple(axes))


def tree_pspecs(axes_tree, mesh: Mesh | None = None):
    """Map a pytree of Ax annotations → pytree of PartitionSpecs."""
    mesh = mesh or current_mesh()
    return jax.tree.map(lambda a: pspec(a.axes, mesh), axes_tree,
                        is_leaf=lambda x: isinstance(x, Ax))


def tree_shardings(axes_tree, mesh: Mesh | None = None):
    mesh = mesh or current_mesh()
    return jax.tree.map(lambda a: NamedSharding(mesh, pspec(a.axes, mesh)),
                        axes_tree, is_leaf=lambda x: isinstance(x, Ax))


def shardings_for(abstract_tree, axes_tree, mesh: Mesh | None = None):
    """Shape-aware shardings: like tree_shardings but pruned per leaf so
    every mesh axis divides its dim (pjit argument contract)."""
    mesh = mesh or current_mesh()

    def f(sds, a):
        spec = prune_pspec(tuple(sds.shape), pspec(a.axes, mesh), mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(f, abstract_tree, axes_tree)


def zero_state_axes(param_axes: Ax) -> Ax:
    """Optimizer-state layout: params' layout with the FSDP dim upgraded to
    the ZeRO axes (pod,data) when the param is embed-sharded."""
    new = tuple("zero" if a == "embed" else a for a in param_axes.axes)
    return Ax(new)


def mesh_devices_summary(mesh: Mesh) -> dict:
    return {
        "axis_names": list(mesh.axis_names),
        "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
        "n_devices": int(np.prod([mesh.shape[a] for a in mesh.axis_names])),
    }
