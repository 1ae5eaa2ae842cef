"""The fleet cell's check at smoke size on the CPU: a sound run is
correct; the control and each fault the cell can have are not."""
import pytest

from _chipbench_faults import run_line, tiny

CELL = "tiny.fleet"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_sound_run_is_correct(root):
    line = run_line(root, CELL)
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert line["device"]["count"] == 1
    assert "setup_s" in line["metrics"]


@pytest.mark.parametrize("fault", ["stale", "halfbatch", "nomix", "swapped"])
def test_broken_timed_path_is_not_correct(root, fault):
    assert run_line(root, CELL, fault)["correct"] is False


def test_control_at_lower_precision_is_not_correct(root):
    assert run_line(root, CELL, dtype="bfloat16")["correct"] is False
