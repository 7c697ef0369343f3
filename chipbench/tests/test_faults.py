"""The check catches a broken timed path.  Each test skips the look for a
chip, drives the rest of a run at a smoke size with one fault planted in
the program's step, and sees ``correct`` come out false:

* a step that returns its state unchanged;
* half of the batch left out, the mean taken over the rest;
* on a (data=2, model=2) mesh, the exchange between chips left out: each
  data shard updates from its own rows' gradient alone.
"""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from chipbench import run
from chipbench.tests.smoke import smoke_cell


def _plant(monkeypatch, wrap):
    import repro.launch.train as lt
    real = lt.make_train_step
    monkeypatch.setattr(lt, "make_train_step",
                        lambda cfg, opt, **kw: wrap(real(cfg, opt, **kw)))


def unchanged(step):
    def broken(params, opt_state, batch, i):
        _, _, metrics = step(params, opt_state, batch, i)
        return params, opt_state, metrics
    return broken


def half_batch(step):
    def broken(params, opt_state, batch, i):
        lab = batch["labels"]
        B, S = lab.shape
        lab = lab.at[B // 2:].set(-1) if B > 1 else lab.at[:, S // 2:].set(-1)
        return step(params, opt_state, dict(batch, labels=lab), i)
    return broken


def no_exchange(step):
    from repro.distributed.sharding import current_mesh, use_mesh

    def local(params, opt_state, batch, i):
        with use_mesh(None):
            return step(params, opt_state, batch, i)

    def broken(params, opt_state, batch, i):
        return jax.shard_map(local, mesh=current_mesh(),
                             in_specs=(P(), P(), P("data"), P()),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(params, opt_state, batch, i)
    return broken


@pytest.mark.parametrize("config,fault,mesh,batch", [
    ("minicpm3-4b", unchanged, None, 1),
    ("minicpm3-4b", half_batch, None, 1),
    ("mamba2-1.3b", unchanged, [2, 2], 2),
    ("mamba2-1.3b", half_batch, [2, 2], 2),
    ("mamba2-1.3b", no_exchange, [2, 2], 2),
])
def test_fault_reads_not_correct(monkeypatch, config, fault, mesh, batch):
    _plant(monkeypatch, fault)
    cell = smoke_cell(config, mesh=mesh, batch=batch)
    res = run.execute(cell, 2**31 + 5, 0.2, False,
                      jax.devices()[:cell.chips])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("config,mesh,batch", [
    ("minicpm3-4b", None, 1), ("mamba2-1.3b", [2, 2], 2)])
def test_sound_run_reads_correct(config, mesh, batch):
    cell = smoke_cell(config, mesh=mesh, batch=batch)
    res = run.execute(cell, 2**31 + 5, 0.2, False,
                      jax.devices()[:cell.chips])
    assert res["correct"], res["checks"]
