#!/usr/bin/env python3
"""Bring-up check of the JAX training path on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # one host with four chips

One chip, in order:
  1. the first device must be a TPU, else exit 1 (no fallback);
  2. the persistent compile cache is turned on (``launch/compile_cache.py``);
  3. gemma3-1b, exactly as the registry holds it, trains for 6 steps at
     batch 2 x 1024 through ``Trainer.fit``; every loss must be finite;
  4. the Pallas flash kernel runs compiled at the model's attention shape,
     forward and backward, and is compared with ``kernels/ref.py``; then 2
     steps with ``use_flash=True`` must compile to a program holding the
     kernel and start from the same loss.

Four chips: 3 steps on device 0 alone, then the same 3 steps on a
``(data=2, model=2)`` mesh of all four; the losses must agree, every
parameter must span the four devices, and each device must have held bytes.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
It is printed only when every check passed.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "gemma3-1b"
SEQ, BATCH = 1024, 2
TRAIN_STEPS, FLASH_STEPS, MESH_STEPS = 6, 2, 3
LOSS_RTOL = 1e-2        # two losses of one bf16 model "agree" within this
TOL = 2e-2      # bfloat16, as tests/test_kernels.py


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def free(trainer) -> None:
    """Drop a trainer's device state (params, optimizer moments)."""
    trainer._last_state = None
    gc.collect()


def losses_agree(a: list[float], b: list[float]) -> bool:
    return len(a) == len(b) and all(
        abs(x - y) <= LOSS_RTOL * abs(y) for x, y in zip(a, b, strict=True))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def train_phase(cfg, shape, steps: int) -> list[float]:
    """Train through Trainer.fit; print each loss and the step times."""
    import jax
    from repro.launch.train import Trainer

    tr = Trainer(cfg, shape)
    logs = tr.fit(steps)
    losses = [m["loss"] for m in logs]
    for m in logs:
        say(f"train step {m['step']} loss {m['loss']!r} "
            f"time_s {m['time_s']!r}")
    # time_s is each step's full period (batch, dispatch, sync, log row)
    times = [m["time_s"] for m in logs]
    med = statistics.median(times[1:])
    say(f"train first_step_s {times[0]!r} (compile + run), "
        f"compile_s ~{times[0] - med!r}, median_step_s {med!r} "
        f"over steps 1..{steps - 1}")
    stats = jax.devices()[0].memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    say(f"train peak_bytes_in_use {peak} of bytes_limit {limit}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    if peak is not None and limit is not None:
        check(peak < limit, f"peak {peak} >= limit {limit}")
    free(tr)
    return losses


def _err(got, want) -> float:
    """max |got - want|, relative to max(1, max |want|)."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    return float(np.max(np.abs(g - w)) / max(1.0, float(np.max(np.abs(w)))))


def _compare(name: str, got, want, tol: float) -> None:
    e = _err(got, want)
    say(f"kernel {name} worst_rel_err {e!r} tol {tol}")
    check(e <= tol, f"{name}: error {e} > {tol}")


def kernel_phase(cfg, batch: int, seq: int) -> None:
    """The flash kernel at the given model widths vs kernels/ref.py."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def rnd(shape, dtype):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    bf = jnp.bfloat16

    # flash attention, forward and backward, at the model's attention shape
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    qkv = (rnd((batch, seq, H, hd), bf), rnd((batch, seq, Kv, hd), bf),
           rnd((batch, seq, Kv, hd), bf))
    do = rnd((batch, seq, H, hd), bf)

    def fwd_bwd(f, do, *qkv):
        o, vjp = jax.vjp(f, *qkv)
        return (o, *vjp(do))

    for window in (None, cfg.window):
        def kern(q, k, v, w=window):
            return ops.flash_attention(q, k, v, True, w)

        def oracle(q, k, v, w=window):
            return ref.flash_attention_ref(q, k, v, causal=True, window=w)

        got = jax.jit(fwd_bwd, static_argnums=0)(kern, do, *qkv)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(fwd_bwd, static_argnums=0)(oracle, do, *qkv)
        for part, g, w in zip(("o", "dq", "dk", "dv"), got, want,
                              strict=True):
            _compare(f"flash_attention {qkv[0].shape} window={window} "
                     f"{part}", g, w, TOL)


def flash_step_phase(cfg, shape, steps: int, ref_loss: float) -> None:
    """Train steps with use_flash=True: the compiled step must hold the
    Pallas kernel and start from the XLA-attention step's loss."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import input_specs
    from repro.launch.train import Trainer

    tr = Trainer(replace(cfg, use_flash=True), shape)
    aparams, aopt = jax.eval_shape(tr.init_state)
    hlo = tr.step_jit.lower(aparams, aopt, input_specs(cfg, shape),
                            jax.ShapeDtypeStruct((), jnp.int32)
                            ).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    say(f"flash step tpu_custom_call count {n_kernels}")
    check(n_kernels > 0, "use_flash step holds no tpu_custom_call")
    logs = tr.fit(steps)
    losses = [m["loss"] for m in logs]
    say(f"flash step losses {losses!r} vs xla first loss {ref_loss!r}")
    check(all(math.isfinite(x) for x in losses), f"non-finite {losses}")
    check(losses_agree(losses[:1], [ref_loss]),
          f"flash first loss {losses[0]} != {ref_loss}")
    free(tr)


def mesh_phase(cfg, shape, steps: int) -> None:
    """Device 0 alone, then a (data=2, model=2) mesh over four devices."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer

    n = 4
    devs = jax.devices()
    check(len(devs) >= n, f"need {n} devices, have {len(devs)}")
    one = Trainer(cfg, shape)
    single = [m["loss"] for m in one.fit(steps)]
    free(one)
    say(f"mesh device0 losses {single!r}")

    tr = Trainer(cfg, shape, make_mesh((2, 2), ("data", "model")))
    meshed = [m["loss"] for m in tr.fit(steps)]
    say(f"mesh data=2,model=2 losses {meshed!r}")
    check(losses_agree(meshed, single), "mesh losses differ from device 0")

    want = set(devs[:n])
    params = jax.tree.leaves(tr._last_state[0])
    spread = sum(set(p.sharding.device_set) == want for p in params)
    say(f"mesh params spanning all {n} devices: {spread} of {len(params)}")
    check(spread == len(params), "a parameter does not span the mesh")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:n]]
    say(f"mesh peak_bytes_in_use per device {peaks}")
    check(all(p > 0 for p in peaks), "a device held no bytes")
    free(tr)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh comparison on four chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (first device: {dev.platform}, "
              f"{dev.device_kind})", file=sys.stderr)
        return 1
    say(f"device platform {dev.platform} kind {dev.device_kind} "
        f"count {len(jax.devices())}")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    say(f"compile cache {cache}")
    cfg = get_config(ARCH)
    shape = ShapeConfig("chip_smoke", seq_len=SEQ, global_batch=BATCH,
                        kind="train")
    t0 = time.time()
    try:
        if args.chips == 4:
            mesh_phase(cfg, shape, MESH_STEPS)
        else:
            losses = train_phase(cfg, shape, TRAIN_STEPS)
            kernel_phase(cfg, BATCH, SEQ)
            flash_step_phase(cfg, shape, FLASH_STEPS, losses[0])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n_cached = sum(1 for _ in cache.rglob("*")) if cache.is_dir() else 0
    say(f"compile cache entries {n_cached}; wall_s {time.time() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
