"""Fault-tolerant training driver.

Responsibilities beyond the jitted step:
  * checkpoint/restart (async writer, atomic commits, exact data resume);
  * failure handling — a failed step re-creates the mesh from surviving
    devices (``best_mesh_for``) and restores the latest checkpoint; a step
    that fails again after that restore re-raises;
  * straggler watchdog — steps exceeding ``straggler_factor ×`` the rolling
    median are logged and counted (on real pods this feeds the controller
    that evicts the slow host; here it guards CI);
  * metrics logging (JSONL).

Run (CPU example, tiny config):
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import time

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
        self.step_times: list[float] = []
        self.stragglers = 0
        self.failures = 0
        self.ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        self._build()

    # -- jit construction ----------------------------------------------------

    def _build(self):
        cfg = self.cfg
        step_fn = make_train_step(cfg, self.opt, grad_accum=self.grad_accum)
        if self.mesh is not None:
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
        params, opt_state, start = self.restore_or_init()
        data = SyntheticDataset(self.cfg, self.shape, seed=self.seed,
                                start_step=start,
                                batch_override=batch_override,
                                seq_override=seq_override)
        logs: list[dict] = []
        log_f = open(log_path, "a") if log_path else None
        step = start
        failed_step = None
        while step < steps:
            batch = next(data)
            t0 = time.time()
            try:
                if inject_failure_at is not None and step == inject_failure_at:
                    inject_failure_at = None
                    raise RuntimeError("injected node failure")
                with use_mesh(self.mesh):
                    params, opt_state, metrics = self.step_jit(
                        params, opt_state, batch, jnp.int32(step))
                    metrics = jax.tree.map(float, jax.device_get(metrics))
            except Exception:  # noqa: BLE001 — node failure path
                self.failures += 1
                if self.ckpt is None or step == failed_step:
                    raise
                failed_step = step
                # re-create mesh from surviving devices + restore
                self.ckpt.wait()
                if self.mesh is not None:
                    n = len(jax.devices())
                    self.mesh = best_mesh_for(n)
                self._build()
                params, opt_state, start_r = self.restore_or_init()
                data = SyntheticDataset.from_state(
                    self.cfg, self.shape, {"step": start_r, "seed": self.seed},
                    batch_override=batch_override, seq_override=seq_override)
                step = start_r
                continue
            dt = time.time() - t0
            self.step_times.append(dt)
            med = statistics.median(self.step_times[-20:])
            if len(self.step_times) > 5 and dt > self.straggler_factor * med:
                self.stragglers += 1
                metrics["straggler"] = dt / med
            metrics.update(step=step, time_s=dt)
            logs.append(metrics)
            if log_f:
                log_f.write(json.dumps(metrics) + "\n")
                log_f.flush()
            step += 1
            if self.ckpt and (step % self.ckpt_every == 0 or step == steps):
                self.ckpt.save(step, {"params": params, "opt": opt_state},
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
