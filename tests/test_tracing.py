"""The span recorder and compile counters of ``Trainer.fit``
(``launch/spans.py``), the named scopes of the model's layers, and the
benchmark's readers of both (``chipbench/metrics/``)."""

import collections
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch import spans
from repro.launch.train import Trainer

SHAPE = ShapeConfig("t", seq_len=64, global_batch=4, kind="train")
LOOP = ["fit.data", "fit.step", "fit.sync", "fit.log"]
METRICS = Path(__file__).resolve().parents[1] / "chipbench" / "metrics"
if str(METRICS.parents[1]) not in sys.path:     # readers import chipbench
    sys.path.insert(0, str(METRICS.parents[1]))


@pytest.fixture(scope="module")
def trainer():
    return Trainer(smoke_config("gemma3-1b"), SHAPE, lr=1e-3)


def test_fit_records_each_step_in_order(trainer):
    trainer.fit(5)
    rec = spans.fits()[-1]
    assert rec.steps == 5
    assert [s.name for s in rec.spans] == ["fit.restore"] + LOOP * 5
    assert [s.step for s in rec.spans][1::4] == list(range(5))
    ends = [s.end_ns for s in rec.spans]
    assert ends == sorted(ends)


def test_time_s_is_the_recorded_period(trainer):
    logs = trainer.fit(4)
    rec = spans.fits()[-1]
    data0 = next(s for s in rec.spans if s.name == "fit.data")
    syncs = [s.end_ns for s in rec.spans if s.name == "fit.sync"]
    expect = [b - a for a, b in zip([data0.start_ns] + syncs, syncs)]
    assert [round(m["time_s"] * 1e9) for m in logs] == expect


def test_records_stay_bounded(monkeypatch):
    monkeypatch.setattr(spans, "MAX_SPANS", 3)
    for _ in range(spans.MAX_FITS + 2):
        with spans.fit() as rec:
            for k in range(5):
                with spans.step(k), spans.span("fit.data"):
                    pass
                rec.steps += 1
    recs = spans.fits()
    assert len(recs) == spans.MAX_FITS
    assert recs[-1] is spans.last_fit(5)
    assert [s.step for s in recs[-1].spans] == [2, 3, 4]


def test_straggler_window_spans_fits():
    """The watchdog's rolling median runs across the fits of one
    ``Trainer``: fits of 3 steps still reach its sixth period."""
    tr = Trainer(smoke_config("gemma3-1b"), SHAPE, lr=1e-3,
                 straggler_factor=0.0)
    tr.fit(3)
    assert tr.stragglers == 0
    logs = tr.fit(3)
    assert tr.stragglers == 1
    assert "straggler" in logs[-1]
    tr.fit(3)
    assert tr.stragglers == 4


def test_compiles_are_charged_to_the_step_span(trainer):
    trainer.fit(2, batch_override=2)          # a batch shape not seen yet
    rec = spans.fits()[-1]
    assert rec.compiles["fit.step"] >= 1
    first_step = next(s for s in rec.spans if s.name == "fit.step")
    assert any(first_step.start_ns <= e.end_ns <= first_step.end_ns
               and e.seconds > 0 for e in rec.events)
    trainer.fit(2, batch_override=2)          # steady: nothing to compile
    assert spans.fits()[-1].compiles["fit.step"] == 0
    last, before = dict(spans.fits()[-1].compiles), spans._compile_s
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))   # outside any fit
    assert dict(spans.fits()[-1].compiles) == last
    with spans.fit() as rec:                    # counted for the next one
        pass
    assert rec.compile_s_before > before


def test_no_span_takes_a_harness_name(tmp_path):
    """The benchmark counts ``dispatch`` spans as steps and opens its window
    with ``window``: the program's spans never take those names."""
    tr = Trainer(smoke_config("gemma3-1b"), SHAPE, lr=1e-3,
                 ckpt_dir=str(tmp_path / "ck"), ckpt_every=2)
    tr.fit(4, inject_failure_at=3)
    names = {s.name for s in spans.fits()[-1].spans}
    assert names == set(LOOP) | {"fit.restore", "fit.ckpt", "fit.recover"}
    assert not names & {"dispatch", "window"}


def test_spans_match_their_profiler_twins(trainer, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        trainer.fit(3)
    ours = [s for s in spans.fits()[-1].spans if s.name == "fit.step"]
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    theirs = sorted((e.start_ns, e.duration_ns)
                    for plane in ProfileData.from_file(str(xplane)).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name == "fit.step")
    assert len(theirs) == len(ours) == 3
    offset = ours[0].start_ns - theirs[0][0]
    for s, (start, dur) in zip(ours, theirs, strict=True):
        assert abs(s.start_ns - offset - start) < 1e6
        assert abs(s.end_ns - s.start_ns - dur) < 1e6


@pytest.mark.parametrize("arch, scopes", [
    ("minicpm3-4b", ["embed", "mixer", "mla", "mlp", "loss", "optimizer"]),
    ("mamba2-1.3b", ["embed", "mixer", "ssd", "ssd/conv", "ssd/intra",
                     "ssd/states", "ssd/scan", "ssd/inter", "loss",
                     "optimizer"]),
])
def test_step_hlo_carries_the_scopes(arch, scopes):
    from repro.data.pipeline import input_specs
    from repro.models.transformer import abstract_params
    cfg = smoke_config(arch)
    tr = Trainer(cfg, SHAPE)
    aparams = abstract_params(cfg)
    hlo = tr.step_jit.lower(aparams, jax.eval_shape(tr.opt.init, aparams),
                            input_specs(cfg, SHAPE),
                            jax.ShapeDtypeStruct((), jnp.int32)).as_text(
        dialect="hlo", debug_info=True)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in scopes:
        assert any(re.search(rf"(^|[/(]){scope}[/)]", n) for n in names), scope


# -- the benchmark's readers ---------------------------------------------------


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}",
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _hand_built(monkeypatch):
    """Two steps, at 10 and 20 ms: data 1-2, step 2-5, sync 5-9, log 9-10
    (ms into the step; the second step's step, sync and log 3 ms later),
    after a restore whose compiles took 3 s; 4 s of compiling before the
    fit, and 2 s in the second step's ``fit.step``."""
    ms = 1_000_000
    rec = spans.Record(compile_s_before=4.0, steps=2)
    rec.spans.append(spans.Span("fit.restore", None, 0, ms))
    for k in range(2):
        t = 10 * ms * (k + 1)
        rec.spans.extend(
            spans.Span(n, k, t + a * ms, t + b * ms) for n, a, b in
            [("fit.data", 1, 2), ("fit.step", 2, 5 + 3 * k),
             ("fit.sync", 5 + 3 * k, 9 + 3 * k),
             ("fit.log", 9 + 3 * k, 10 + 3 * k)])
    rec.events += [spans.CompileEvent(ms // 2, 3.0),
                   spans.CompileEvent(24 * ms, 2.0)]
    rec.compiles["fit.restore"] = rec.compiles["fit.step"] = 1
    monkeypatch.setattr(spans, "_fits", collections.deque([rec]))


@pytest.mark.parametrize("name, value", [
    ("host_gap_ms", 9.0),           # end of step 1 (28) - end of sync 0 (19)
    ("host_data_ms", 1.0),
    ("window_compiles", 1),
    ("setup_compile_s", 7.0),       # 4 before the fit + 3 in the restore
])
def test_reader_on_a_hand_built_record(monkeypatch, name, value):
    _hand_built(monkeypatch)
    read = _reader(name)
    assert read({"trace": {"steps": 2}}) == pytest.approx(value)
    with pytest.raises(ValueError, match="expected 3"):
        read({"trace": {"steps": 3}})


# -- the mesh cell's readers on the recorded four-device trace -----------------

BENCH = METRICS.parent


def _recorded(chips: int) -> dict:
    import json
    from chipbench import trace
    ev = json.loads((BENCH / "tests" / "data" / f"tiny_trace_{chips}.json")
                    .read_text())
    return trace.reduce(ev, chips)


@pytest.mark.parametrize("chips, value", [
    (4, 0.05880675),    # 235.227 us exposed on device 0 over 4 steps
    (1, 0.0),           # one chip: no collective
])
def test_collective_exposed_ms_on_a_recorded_trace(chips, value):
    got = _reader("collective_exposed_ms")({"trace": _recorded(chips)})
    assert got == pytest.approx(value)


def test_train_mfu_mesh4_on_the_recorded_four_device_trace():
    import json
    from types import SimpleNamespace
    from chipbench import cells
    tr = _recorded(4)
    conf = json.loads((BENCH / "configs" / "mamba2-1.3b.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "train.s4096.b2.mesh2x2.json")
                         .read_text())
    model = cells.load_module(BENCH / "configs" / "mamba2-1.3b.py")
    cell = SimpleNamespace(config=conf, traffic=traffic, model=model)
    ctx = {"cell": cell, "trace": tr, "tokens_per_step": 2 * 4096,
           "devices": [None] * 4, "peaks": {"bf16_flops": 197e12}}
    flops = model.flops_per_token(conf, 4096)
    want = 100 * flops * 8192 * tr["steps"] / tr["window_s"] / (4 * 197e12)
    got = _reader("train_mfu_mesh4")(ctx)
    assert got == pytest.approx(want)
    assert got == _reader("train_mfu")(ctx)
