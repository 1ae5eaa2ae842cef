"""The numbers that decide ``correct``, against hand counts."""
import math

import numpy as np
import pytest

import _chipbench_tiny  # noqa: F401  (puts the harness on the path)
from chipbench import compare


def test_leaf_gap_takes_the_larger_of_the_leaf_and_the_median_leaf():
    ref = {"a": 10.0, "b": 2.0, "c": 4.0}
    prog = {"a": 10.5, "b": 2.4, "c": 4.0}
    # median leaf 4: a's gap 0.5 / 10, b's 0.4 / 4 (its own 2 is smaller)
    assert compare.leaf_gaps(prog, ref) == pytest.approx({"a": 0.05, "b": 0.1, "c": 0.0})
    assert compare.norm_gap(prog, ref) == pytest.approx(0.1)
    assert compare.norm_gap(prog, ref, ["a"]) == pytest.approx(0.05)
    assert compare.norm_gap({"a": 1.0}, ref) == math.inf


def test_row_gap_sees_one_agent_that_the_stack_dilutes():
    ref = {"w": np.full(1000, 3.0), "c": np.full(1000, 1.0)}
    prog = {"w": ref["w"].copy(), "c": ref["c"].copy()}
    prog["w"][7] = 3.3  # one agent of a thousand, 10% off
    stacked = lambda rows: {k: float(np.linalg.norm(v)) for k, v in rows.items()}
    assert compare.norm_gap(stacked(prog), stacked(ref)) < 2e-4
    # the median leaf's median row is 2: w's row 7 reads 0.3 / 3
    assert compare.row_gap(prog, ref) == pytest.approx(0.1)
    prog["c"][3] = 1.1  # c's rows are under the median's 2: 0.1 / 2
    assert compare.row_gap(prog, ref, ["c"]) == pytest.approx(0.05)
    assert compare.row_gap({"w": prog["w"][:10], "c": prog["c"]}, ref) == math.inf


def test_loss_gap_and_leaves_left_out():
    assert compare.loss_gap([1.1, 2.0], [1.0, 2.0]) == pytest.approx(0.1)
    assert compare.loss_gap([1.0], [1.0, 2.0]) == math.inf
    grads = {"w": 1.0, "v": 2.0, "bias_under_softmax": 1e-9}
    assert compare.moving_leaves(grads) == ["v", "w"]


def test_a_number_that_is_not_finite_fails():
    checks = compare.checks({"g": float("nan"), "l": 0.0}, {"g": 1.0, "l": 1.0})
    assert not compare.passed(checks)
    with pytest.raises(KeyError):
        compare.checks({"g": 0.0}, {"g": 1.0, "l": 1.0})
