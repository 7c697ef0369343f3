"""The reduction from a trace's events to device numbers: on events whose
answers are worked out by hand, and on small traces recorded on one and on
four v5e chips (``data/``)."""

import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"

HAND = {
    "ops": {"0": [["fusion.1", "fusion", 0, 10],
                  ["all-gather-start.2", "all-gather-start", 5, 20],
                  ["fusion.3", "fusion", 30, 40],
                  ["while.4", "while", 0, 45]],
            "1": [["fusion.1", "fusion", 0, 5]]},
    "spans": [["window", 0, 50], ["dispatch", 0, 1], ["dispatch", 25, 26],
              ["other", 0, 50]],
}


def test_reduce_by_hand():
    got = trace.reduce(HAND, 2)
    assert got["window_s"] == pytest.approx(50e-9)
    assert got["busy_s"] == pytest.approx((30 + 5) / 2 * 1e-9)
    assert got["busiest_device"] == "0"
    assert got["idle_pct"] == pytest.approx(40.0)
    # the all-gather covers [5, 20]; compute overlaps [5, 10]
    assert got["collective_exposed_s"] == pytest.approx(10e-9)
    assert got["steps"] == 2
    assert got["device_ops"][0] == ["all-gather-start.2", pytest.approx(15e-9)]
    assert sorted(got["idle_gaps"]) == [["dispatch", pytest.approx(10e-9)],
                                        ["host loop", pytest.approx(10e-9)]]


def test_window_clips_operations():
    ev = {"ops": {"0": [["fusion", "fusion", -10, 5],
                        ["fusion", "fusion", 45, 70]]},
          "spans": [["dispatch", 0, 1], ["window", 0, 50]]}
    got = trace.reduce(ev, 1)
    assert got["busy_s"] == pytest.approx(10e-9)
    assert got["idle_pct"] == pytest.approx(80.0)


def test_parse_op():
    text = ("%fusion.7 = (bf16[40,2048]{1,0:T(8,128)(2,1)S(1)}, f32[2]{0}) "
            "fusion(bf16[6,40]{1,0} %all-gather.3), kind=kLoop")
    assert trace.parse_op(text) == (
        "fusion.7 fusion (bf16[40,2048], f32[2])", "fusion")
    assert not trace.is_collective(*trace.parse_op(text))
    ag = "%all-gather-start.1 = bf16[8]{0} all-gather-start(bf16[2]{0} %p)"
    assert trace.is_collective(*trace.parse_op(ag))


def test_no_device_operations_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"ops": {}, "spans": HAND["spans"]}, 1)


@pytest.mark.parametrize("chips", [1, 4])
def test_recorded_chip_trace(chips):
    ev = json.loads((DATA / f"tiny_trace_{chips}.json").read_text())
    got = trace.reduce(ev, chips)
    assert got["steps"] == 4
    assert 0 < got["busy_s"] <= got["window_s"]
    assert 0 <= got["idle_pct"] < 100
    assert len(ev["ops"]) == chips
    assert got["device_ops"] and len(got["idle_gaps"]) <= 10
    n_coll = sum(trace.is_collective(n, op)
                 for ops in ev["ops"].values() for n, op, _, _ in ops)
    assert (n_coll > 0) == (chips > 1)
    assert 0 <= got["collective_exposed_s"] <= got["busy_s"] * chips
