"""Cell builder: (architecture × input-shape × mesh) → lowered+compiled
XLA program + roofline raw numbers.  Shared by launch/dryrun.py, the
benchmarks and the sharding tests (which run it on tiny meshes).
"""

from __future__ import annotations

import math
import re
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..configs import get_config, get_shape
from ..data.pipeline import input_axes, input_specs
from ..distributed.sharding import rules_override, shardings_for, use_mesh
from ..models.layers import abstract
from ..models.transformer import (abstract_params, cache_axes, cache_specs,
                                  forward_hidden, param_axes,
                                  unembed_weight)
from ..optim.optimizers import make_optimizer
from ..training.train_step import make_serve_step, make_train_step

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def args_prefill(aparams, abatch):
    return (aparams, abatch["inputs"])

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "f64": 8, "u16": 2, "s16": 2,
                "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "u64": 8}


def _batch_override_needed(shape, mesh) -> bool:
    bsh = math.prod(int(mesh.shape[a]) for a in ("pod", "data")
                    if a in mesh.axis_names)
    return shape.global_batch % bsh != 0


@dataclass
class CellResult:
    arch: str
    shape: str
    mesh_axes: list
    mesh_shape: list
    kind: str
    lower_s: float = 0.0
    compile_s: float = 0.0
    flops: float = 0.0
    hlo_bytes: float = 0.0
    peak_bytes_per_device: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    collective_bytes: dict = field(default_factory=dict)
    collective_total: float = 0.0
    n_collectives: int = 0
    error: str = ""

    def to_json(self) -> dict:
        return dict(self.__dict__)


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype, 4)
    if not dims:
        return b
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n * b


_COLL_RE = re.compile(
    r"(\w+\[[^\]]*\][^=]*?)=\s*\S*(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)")
_TYPE_RE = re.compile(r"(\w+)\[([0-9, ]*)\]")


def collective_bytes_from_hlo(hlo_text: str) -> tuple[dict, int]:
    """Sum result-operand bytes of every collective op in (partitioned) HLO.
    Returns ({collective: bytes}, n_ops)."""
    out = {c: 0 for c in COLLECTIVES}
    n = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?\S+\s*=\s*(.*)", s)
        if not m:
            continue
        rhs = m.group(1)
        coll = None
        for c in COLLECTIVES:
            if re.search(rf"\b{c}(-start|-done)?\(", rhs):
                coll = c
                break
        if coll is None or f"{coll}-done(" in rhs:
            continue   # count -start, skip -done (same buffer)
        n += 1
        total = 0
        # result type may be a tuple: sum all array components; types appear
        # before the op name (which may itself be preceded by '(' for tuples)
        head = rhs[:rhs.find(coll)]
        for dt, dims in _TYPE_RE.findall(head):
            if dt in _DTYPE_BYTES:
                total += _shape_bytes(dt, dims)
        out[coll] += total
    return out, n


# ---------------------------------------------------------------------------


def build_step(arch: str, shape_name: str, mesh, variant: dict | None = None):
    """Returns (fn, args_abstract, in_shardings, donate, meta) for the cell.

    ``variant`` (perf-iteration knobs): dict with optional keys
    remat / attn_chunked / loss_chunk / state_dtype / grad_accum overrides.
    """
    from dataclasses import replace as dc_replace

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    variant = variant or {}
    cfg_over = {k: v for k, v in variant.items()
                if k in ("remat", "attn_chunked", "loss_chunk", "state_dtype",
                         "attn_chunk", "n_layers", "scan_unroll",
                         "use_flash", "seq_sharded_acts")}
    if cfg_over:
        cfg = dc_replace(cfg, **cfg_over)

    p_ax = param_axes(cfg)
    aparams = abstract_params(cfg)
    b_ax = input_axes(cfg, shape)
    abatch = input_specs(cfg, shape)

    if shape.kind == "train":
        opt = make_optimizer(variant.get("optimizer", "adamw"),
                             state_dtype=cfg.state_dtype)
        step_fn = make_train_step(cfg, opt,
                                  grad_accum=variant.get("grad_accum", 1),
                                  accum_dtype=variant.get("accum_dtype",
                                                          "float32"))
        aopt = jax.eval_shape(opt.init, aparams)
        o_ax = opt.state_axes(p_ax)
        shardings = (shardings_for(aparams, p_ax, mesh),
                     shardings_for(aopt, o_ax, mesh),
                     shardings_for(abatch, b_ax, mesh), None)
        args = (aparams, aopt, abatch, jax.ShapeDtypeStruct((), jnp.int32))
        return step_fn, args, shardings, (0, 1), dict(cfg=cfg, shape=shape)

    if shape.kind == "prefill":
        def prefill_fn(params, inputs):
            hidden, _ = forward_hidden(params, cfg, inputs)
            last = hidden[:, -1:]
            return last @ unembed_weight(params, cfg)
        b_shard = shardings_for(abatch, b_ax, mesh)["inputs"]
        return prefill_fn, args_prefill(aparams, abatch), \
            (shardings_for(aparams, p_ax, mesh), b_shard), (), \
            dict(cfg=cfg, shape=shape)

    # decode: one token against a seq_len KV cache
    shard_kv_seq = _batch_override_needed(shape, mesh)
    cs = cache_specs(cfg, shape.global_batch, shape.seq_len,
                     shard_kv_seq=shard_kv_seq)
    acache = abstract(cs)
    c_ax = cache_axes(cfg, shape.global_batch, shape.seq_len,
                      shard_kv_seq=shard_kv_seq)
    serve = make_serve_step(cfg)
    shardings = (shardings_for(aparams, p_ax, mesh),
                 shardings_for(acache, c_ax, mesh),
                 shardings_for(abatch, b_ax, mesh)["inputs"], None)
    args = (aparams, acache, abatch["inputs"],
            jax.ShapeDtypeStruct((), jnp.int32))
    return serve, args, shardings, (1,), dict(cfg=cfg, shape=shape)


def lower_cell(arch: str, shape_name: str, mesh,
               variant: dict | None = None):
    """Lower + compile one cell; returns (CellResult, compiled|None)."""
    shape = get_shape(shape_name)
    res = CellResult(arch=arch, shape=shape_name,
                     mesh_axes=list(mesh.axis_names),
                     mesh_shape=[int(mesh.shape[a]) for a in mesh.axis_names],
                     kind=shape.kind)
    overrides = {}
    if _batch_override_needed(shape, mesh):
        overrides = dict(batch=(), kv_seq=("pod", "data", "model"))
    try:
        with use_mesh(mesh), rules_override(**overrides):
            fn, args, shardings, donate, meta = build_step(
                arch, shape_name, mesh, variant)
            jitted = jax.jit(fn, in_shardings=shardings,
                             donate_argnums=donate)
            t0 = time.time()
            lowered = jitted.lower(*args)
            res.lower_s = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            res.compile_s = time.time() - t0

        ca = compiled.cost_analysis() or {}
        res.flops = float(ca.get("flops", 0.0))
        res.hlo_bytes = float(ca.get("bytes accessed", 0.0))
        ma = compiled.memory_analysis()
        if ma is not None:
            res.argument_bytes = float(getattr(ma, "argument_size_in_bytes", 0))
            res.output_bytes = float(getattr(ma, "output_size_in_bytes", 0))
            res.temp_bytes = float(getattr(ma, "temp_size_in_bytes", 0))
            res.peak_bytes_per_device = res.argument_bytes + res.temp_bytes
        txt = compiled.as_text()
        res.collective_bytes, res.n_collectives = \
            collective_bytes_from_hlo(txt)
        res.collective_total = float(sum(res.collective_bytes.values()))
        return res, compiled
    except Exception as e:  # noqa: BLE001 — record, let the driver continue
        res.error = f"{type(e).__name__}: {e}"
        return res, None
