"""The training cells: set-up, the measured window, the check.

One run drives the program's own entry, ``Trainer.fit``, on one object:

1. set-up builds the ``Trainer`` (its jitted step, on the cell's mesh) and
   calls ``fit(3)``: that compiles the step, or loads it from the compile
   cache, and trains the seed's first three steps.  The harness keeps the
   numbers the check needs: the first gradient, read from AdamW's first
   moment after step 1, and the change of every parameter after step 3;
2. the window is one more ``fit(n)`` on the same object, sized from the
   set-up's step time to fill ``--seconds``.  ``fit`` starts again from the
   seed, so the window's first three steps are the checked ones;
3. after the window: the device's peak memory, then the program's state is
   dropped and the plain float32 reference trains the same three steps.

The harness wraps the trainer's jitted step in a recorder of host
timestamps; it adds no synchronisation of its own (``fit`` already waits
for each step's metrics).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench import check, plain, trace

B1 = plain.B1     # AdamW's first-moment decay: m after step 1 = (1 - B1) g
CHECK_STEPS = 3


def log(t_start: float, msg: str) -> None:
    print(f"chipbench: {time.time() - t_start:8.2f} s  {msg}",
          file=sys.stderr, flush=True)


def program_config(cell):
    """The program's ModelConfig: the registry's entry with every field the
    configuration file fixes set from it."""
    from repro.configs import get_config
    base = get_config(cell.config["arch"])
    fields = {}
    for k, v in cell.model.program_fields(cell.config).items():
        cur = getattr(base, k)
        if isinstance(v, dict):
            v = type(cur)(**v)
        elif isinstance(cur, tuple):
            v = tuple(v)
        fields[k] = v
    return dataclasses.replace(base, **fields)


class StepRecorder:
    """Stands in for ``Trainer.step_jit``: notes the host time of each call,
    marks it as a ``dispatch`` span for the profiler, and hands the
    outputs of the first call to ``after_first`` (set-up only)."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list[float] = []
        self.after_first = None
        self.arg_shapes = None

    def __call__(self, *args):
        import jax
        if self.arg_shapes is None:     # the jit's own shardings apply
            self.arg_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        with jax.profiler.TraceAnnotation("dispatch"):
            self.calls.append(time.perf_counter())
            out = self.inner(*args)
        if self.after_first is not None and len(self.calls) == 1:
            self.after_first(out)
        return out

    def compiled(self):
        """The step as compiled for these arguments (from the cache)."""
        return self.inner.lower(*self.arg_shapes).compile()


def _by_leaf(tree) -> dict:
    """{leaf path: float} of a tree of scalars."""
    import jax
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def state_bytes_per_device(tree) -> dict:
    """{device id: bytes of the shards of ``tree`` that live there}."""
    import jax
    out: dict = {}
    for a in jax.tree.leaves(tree):
        for s in a.addressable_shards:
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return out


def build(cell, seed: int, devices):
    """The Trainer for this cell: the configuration on its mesh."""
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer
    t = cell.traffic
    shape = ShapeConfig(cell.name, seq_len=t["seq_len"],
                        global_batch=t["global_batch"], kind="train")
    mesh = make_mesh(tuple(t["mesh"]), ("data", "model")) \
        if t.get("mesh") else None
    return Trainer(program_config(cell), shape, mesh, seed=seed)


def program_readings(trainer, rec) -> dict:
    """Set-up: ``fit(3)`` from the seed, with the numbers the check needs.

    Returns the losses, the norm of each leaf of the first gradient as AdamW
    got it (its first moment after step 1, over 1 - b1), the norm of each
    leaf's change after step 3, and the host times of the calls."""
    import jax
    from repro.models.transformer import init_params
    got = {}

    def first(out):
        got["grad"] = {k: math.sqrt(v) / (1 - B1) for k, v in
                       _by_leaf(plain.sq_norms(out[1]["m"])).items()}

    rec.after_first = first
    logs = trainer.fit(CHECK_STEPS)
    rec.after_first = None
    params3 = trainer._last_state[0]
    trainer._last_state = None          # drop the optimizer state first
    gc.collect()
    params0 = jax.jit(lambda k: init_params(trainer.cfg, k),
                      out_shardings=trainer.p_sh)(
        jax.random.PRNGKey(trainer.seed))
    change = {k: math.sqrt(v) for k, v in
              _by_leaf(plain.sq_change(params3, params0)).items()}
    del params0, params3
    gc.collect()
    return {"loss": [m["loss"] for m in logs], "grad_norm": got["grad"],
            "update_norm": change, "calls": list(rec.calls)}


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        devices) -> dict:
    """One run of a training cell.  ``t_start``: the process's start, in
    ``time.time()`` seconds.  Returns the result's fields."""
    import jax
    t = cell.traffic
    tokens_per_step = t["global_batch"] * t["seq_len"]
    trainer = build(cell, seed, devices)
    rec = StepRecorder(trainer.step_jit)
    trainer.step_jit = rec
    log(t_start, "trainer built")
    prog = program_readings(trainer, rec)
    log(t_start, f"fit({CHECK_STEPS}) and its readings done; step times "
        f"{np.diff(prog['calls']).round(4).tolist()} s")

    # the set-up's last step sizes the window
    est = prog["calls"][-1] - prog["calls"][-2]
    if traced:
        n = max(t["min_trace_steps"], round(t["trace_seconds"] / est))
    else:
        n = max(t["min_window_steps"], round(seconds / est))
    rec.calls.clear()
    tdir = Path(tempfile.mkdtemp(prefix="chipbench-trace-")) \
        if traced else None
    if traced:
        jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("window"):
        logs = trainer.fit(n)
        t_close = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    log(t_start, f"window of {n} steps closed")
    calls = list(rec.calls)
    t_open = calls[0]
    setup_s = t_open + (time.time() - time.perf_counter()) - t_start

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    losses = [m["loss"] for m in logs]
    out = {"attempted": len(logs),
           "failed": sum(not math.isfinite(x) for x in losses),
           "memory_peak_bytes": int(peak)}
    periods = np.diff(np.asarray(calls + [t_close]))
    window_s = t_close - t_open
    out["e2e"] = {
        "train_tokens_per_s": tokens_per_step * len(logs) / window_s,
        "train_step_ms_p90": float(np.percentile(periods, 90) * 1e3),
        "peak_hbm_gib": peak / 2**30,
        "setup_s": setup_s,
    }
    out["window_losses"] = losses[:CHECK_STEPS]

    ctx = None
    if traced:
        ctx = {"cell": cell, "devices": devices,
               "tokens_per_step": tokens_per_step,
               "trace": trace.reduce(trace.load(tdir), len(devices)),
               "temp_bytes": rec.compiled().memory_analysis()
               .temp_size_in_bytes,
               "state_bytes": max(state_bytes_per_device(
                   trainer._last_state).values())}
        shutil.rmtree(tdir, ignore_errors=True)
        log(t_start, "trace read")

    # the program's state goes before the reference runs
    trainer._last_state = None
    del trainer, rec, logs
    gc.collect()

    model = cell.model.model(cell.config)
    batches = [plain.tokens(model.vocab, t["global_batch"], t["seq_len"], s,
                            seed) for s in range(CHECK_STEPS)]
    ref = plain.train(model, seed, batches, plain.Arith(), devices)
    log(t_start, "reference done")
    prog["loss"] = out["window_losses"]
    out["checks"] = check.compare(prog, ref, cell.limits)
    out["ctx"] = ctx
    return out
