"""The compared numbers: a loss that is not finite, a gradient that is not
finite, or a missing leaf reads as infinity, so it fails any limit."""

import math

from chipbench import check

REF = {"loss": [2.0, 2.0, 2.0], "grad_norm": {"a": 1.0, "b": 1e-9},
       "update_norm": {"a": 1.0, "b": 5.0}}


def _prog(**kw):
    out = {"loss": [2.0, 2.0, 2.0], "grad_norm": {"a": 1.0, "b": 1e-9},
           "update_norm": {"a": 1.0, "b": 5.0}}
    out.update(kw)
    return out


def test_equal_readings_are_zero():
    assert check.readings(_prog(), REF) == {
        "loss_gap": 0.0, "grad_gap": 0.0, "update_gap": 0.0}


def test_nan_and_missing_read_infinite():
    assert check.readings(_prog(loss=[2.0, math.nan, 2.0]),
                          REF)["loss_gap"] == math.inf
    assert check.readings(_prog(grad_norm={"a": math.nan, "b": 1e-9}),
                          REF)["grad_gap"] == math.inf
    assert check.readings(_prog(update_norm={"a": 1.0}),
                          REF)["update_gap"] == math.inf


def test_gap_is_relative_to_the_median_leaf_at_least():
    # leaf b's gradient is nought to rounding: its update is left out, and
    # its own gradient gap is measured against the median leaf's norm
    got = check.readings(_prog(grad_norm={"a": 1.0, "b": 0.1},
                               update_norm={"a": 1.0, "b": 0.0}), REF)
    assert math.isclose(got["grad_gap"], 0.2, rel_tol=1e-6)
    assert got["update_gap"] == 0.0


def test_compare_and_passed():
    checks = check.compare(_prog(loss=[2.0, 2.2, 2.0]), REF,
                           {"loss_gap": 0.05, "grad_gap": 0.1})
    assert checks["loss_gap"]["value"] > 0.05
    assert not check.passed(checks)
