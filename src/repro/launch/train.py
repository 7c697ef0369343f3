"""Fault-tolerant training driver.

Responsibilities beyond the jitted step:
  * checkpoint/restart (async writer, atomic commits, exact data resume);
  * failure handling — a failed step re-creates the mesh from surviving
    devices (``best_mesh_for``) and restores the latest checkpoint; a step
    that fails again after that restore re-raises;
  * straggler watchdog — steps exceeding ``straggler_factor ×`` the rolling
    median of the last 20 periods (``time_s``, across the fits of one
    ``Trainer``) are logged and counted (on real pods this feeds the controller
    that evicts the slow host; here it guards CI);
  * metrics logging (JSONL): a row per step, whose ``time_s`` is the step's
    full period on the span recorder's clock (end of ``fit.sync`` to end of
    ``fit.sync``; the first from the start of ``fit.data``);
  * spans and compile counters (``launch/spans.py``, always on): ``fit``
    opens one record; each loop iteration runs under a
    ``StepTraceAnnotation("train")`` and is cut into the spans
    ``fit.data`` (``next(data)``: the batch and its copy to the device),
    ``fit.step`` (the call of the jitted step), ``fit.sync`` (``device_get``
    of the metrics), ``fit.log`` (straggler check, log row, JSONL write),
    ``fit.ckpt`` (``ckpt.save``'s snapshot) and ``fit.recover`` (the
    failure path); ``fit.restore`` wraps ``restore_or_init``.  Backend
    compiles (persistent-cache loads included) are counted against the
    innermost open span; compile seconds are summed for the process.  No
    span is called ``dispatch`` or ``window``: those names belong to the
    benchmark harness's own spans.

Run (CPU example, tiny config):
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import statistics

import jax
import jax.numpy as jnp

from ..ckpt.store import AsyncCheckpointer, latest_step, load_checkpoint
from ..configs import get_config, get_shape, smoke_config
from ..data.pipeline import SyntheticDataset, input_axes
from ..distributed.sharding import (shardings_for, use_mesh)
from ..models.layers import abstract
from ..models.transformer import init_params, param_axes, param_specs
from ..optim.optimizers import make_optimizer, warmup_cosine
from ..training.train_step import make_train_step
from .compile_cache import enable_compile_cache
from .mesh import best_mesh_for, make_mesh
from . import spans


class Trainer:
    def __init__(self, cfg, shape, mesh=None, optimizer: str = "adamw",
                 lr: float = 3e-4, grad_accum: int = 1,
                 ckpt_dir: str | None = None, ckpt_every: int = 50,
                 seed: int = 0, straggler_factor: float = 3.0):
        self.cfg, self.shape, self.mesh = cfg, shape, mesh
        self.opt = make_optimizer(optimizer, warmup_cosine(lr),
                                  state_dtype=cfg.state_dtype) \
            if optimizer == "adamw" else make_optimizer(optimizer, lr)
        self.grad_accum = grad_accum
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.seed = seed
        self.straggler_factor = straggler_factor
        self.periods = collections.deque(maxlen=20)  # the watchdog's window
        self.stragglers = 0
        self.failures = 0
        self.ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        self._build()

    # -- jit construction ----------------------------------------------------

    def _build(self):
        cfg = self.cfg
        step_fn = make_train_step(cfg, self.opt, grad_accum=self.grad_accum)
        if self.mesh is not None:
            mesh, unmeshed = self.mesh, step_fn

            def step_fn(*args):
                # the model's activation constraints read the active mesh
                # while tracing: without it ``step_jit.lower`` from outside
                # ``use_mesh`` builds another, unsharded program
                with use_mesh(mesh):
                    return unmeshed(*args)

            with use_mesh(self.mesh):
                p_ax = param_axes(cfg)
                aparams = abstract(param_specs(cfg))
                aopt = jax.eval_shape(self.opt.init, aparams)
                b_ax = input_axes(cfg, self.shape)
                from ..data.pipeline import input_specs
                abatch = input_specs(cfg, self.shape)
                self.p_sh = shardings_for(aparams, p_ax, self.mesh)
                self.o_sh = shardings_for(aopt, self.opt.state_axes(p_ax),
                                          self.mesh)
                b_sh = shardings_for(abatch, b_ax, self.mesh)
                self.step_jit = jax.jit(step_fn,
                                        in_shardings=(self.p_sh, self.o_sh,
                                                      b_sh, None),
                                        out_shardings=(self.p_sh, self.o_sh,
                                                       None),
                                        donate_argnums=(0, 1))
        else:
            self.p_sh = self.o_sh = None
            self.step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    def init_state(self):
        """Parameters and optimizer state, each made where it lives: under
        a mesh every device builds only its own shards."""
        with use_mesh(self.mesh):
            params = jax.jit(functools.partial(init_params, self.cfg),
                             out_shardings=self.p_sh)(
                jax.random.PRNGKey(self.seed))
            opt_state = jax.jit(self.opt.init,
                                out_shardings=self.o_sh)(params)
        return params, opt_state

    # -- restore -------------------------------------------------------------

    def restore_or_init(self):
        params, opt_state = self.init_state()
        start = 0
        if self.ckpt_dir and latest_step(self.ckpt_dir) is not None:
            tmpl = {"params": params, "opt": opt_state}
            sh = {"params": self.p_sh, "opt": self.o_sh} \
                if self.p_sh is not None else None
            tree, manifest = load_checkpoint(self.ckpt_dir, tmpl,
                                             shardings=sh)
            params, opt_state = tree["params"], tree["opt"]
            start = manifest["step"]
        return params, opt_state, start

    # -- the loop ------------------------------------------------------------

    def fit(self, steps: int, batch_override: int | None = None,
            seq_override: int | None = None, log_path: str | None = None,
            inject_failure_at: int | None = None) -> list[dict]:
        with spans.fit() as rec:
            with spans.span("fit.restore"):
                params, opt_state, start = self.restore_or_init()
            data = SyntheticDataset(self.cfg, self.shape, seed=self.seed,
                                    start_step=start,
                                    batch_override=batch_override,
                                    seq_override=seq_override)
            logs: list[dict] = []
            log_f = open(log_path, "a") if log_path else None
            step = start
            failed_step = None
            last_ns = None      # where the current step's period began
            while step < steps:
                with spans.step(step):
                    with spans.span("fit.data") as fetched:
                        batch = next(data)
                    if last_ns is None:
                        last_ns = fetched.start_ns
                    try:
                        with spans.span("fit.step"):
                            if inject_failure_at is not None and \
                                    step == inject_failure_at:
                                inject_failure_at = None
                                raise RuntimeError("injected node failure")
                            params, opt_state, metrics = self.step_jit(
                                params, opt_state, batch, jnp.int32(step))
                        with spans.span("fit.sync") as synced:
                            metrics = jax.tree.map(float,
                                                   jax.device_get(metrics))
                    except Exception:  # noqa: BLE001 — node failure path
                        self.failures += 1
                        if self.ckpt is None or step == failed_step:
                            raise
                        failed_step = step
                        with spans.span("fit.recover"):
                            # re-create mesh from surviving devices + restore
                            self.ckpt.wait()
                            if self.mesh is not None:
                                n = len(jax.devices())
                                self.mesh = best_mesh_for(n)
                            self._build()
                            with spans.span("fit.restore"):
                                params, opt_state, start_r = \
                                    self.restore_or_init()
                            data = SyntheticDataset.from_state(
                                self.cfg, self.shape,
                                {"step": start_r, "seed": self.seed},
                                batch_override=batch_override,
                                seq_override=seq_override)
                        step = start_r
                        last_ns = None
                        continue
                    with spans.span("fit.log"):
                        dt = (synced.end_ns - last_ns) / 1e9
                        last_ns = synced.end_ns
                        rec.steps += 1
                        self.periods.append(dt)
                        med = statistics.median(self.periods)
                        if len(self.periods) > 5 and \
                                dt > self.straggler_factor * med:
                            self.stragglers += 1
                            metrics["straggler"] = dt / med
                        metrics.update(step=step, time_s=dt)
                        logs.append(metrics)
                        if log_f:
                            log_f.write(json.dumps(metrics) + "\n")
                            log_f.flush()
                    step += 1
                    if self.ckpt and (step % self.ckpt_every == 0
                                      or step == steps):
                        with spans.span("fit.ckpt"):
                            self.ckpt.save(
                                step, {"params": params, "opt": opt_state},
                                extra={"arch": self.cfg.name})
            if self.ckpt:
                self.ckpt.wait()
            if log_f:
                log_f.close()
            self._last_state = (params, opt_state)
            return logs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--log", default="")
    ap.add_argument("--mesh", default="none",
                    help="none | dxm (e.g. 2x4) using host devices")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = get_shape(args.shape)
    mesh = None
    if args.mesh != "none":
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    tr = Trainer(cfg, shape, mesh, optimizer=args.optimizer, lr=args.lr,
                 grad_accum=args.grad_accum,
                 ckpt_dir=args.ckpt_dir or None)
    logs = tr.fit(args.steps, batch_override=args.batch or None,
                  seq_override=args.seq or None, log_path=args.log or None)
    first, last = logs[0], logs[-1]
    print(f"steps={len(logs)} loss {first['loss']:.4f} -> {last['loss']:.4f} "
          f"stragglers={tr.stragglers} failures={tr.failures}")


if __name__ == "__main__":
    main()
