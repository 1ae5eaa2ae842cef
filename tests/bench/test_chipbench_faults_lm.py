"""The LM cell's check at smoke size on the CPU: a sound run through the
harness is correct; the bfloat16 control and each fault of the timed path
are not."""
import pytest

import _chipbench_lm as lm
from _chipbench_faults import run_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lm.make_root(tmp_path_factory.mktemp("bench"))


def test_sound_run_is_correct(root):
    line = run_line(root, lm.NAME)
    assert line["correct"] is True
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert {"setup_s", "fleet_rounds_per_s"} <= set(line["metrics"])
    assert max(c["value"] for c in line["checks"].values()) < 1e-5


@pytest.mark.parametrize("fault, dtype", [
    (None, "bfloat16"), ("stale", None), ("halfbatch", None), ("nomix", None), ("swapped", None),
], ids=["control", "stale", "halfbatch", "nomix", "swapped"])
def test_control_and_broken_timed_path_are_not_correct(root, fault, dtype):
    assert run_line(root, lm.NAME, fault, dtype)["correct"] is False
