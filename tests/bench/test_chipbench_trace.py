"""The benchmark's reduction from a profiler trace to device busy time, top
operations and idle gaps by host phase: on hand-made events, and on a small
trace recorded on a TPU v5e (``benchmarks/chip/capture_trace.py``)."""
import json
from pathlib import Path

import pytest

import _chipbench_tiny  # noqa: F401  (puts the harness on the path)
from chipbench.trace import reduce_events, reduce_xplane, union_length

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def test_union_merges_overlaps_and_keeps_gaps():
    length, merged = union_length([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert length == 35
    assert merged == [(0, 20), (30, 45)]


def test_reduction_by_hand():
    host = [("bench.window", 10 * MS, 110 * MS),
            ("bench.dispatch", 10 * MS, 12 * MS),
            ("bench.sync", 12 * MS, 60 * MS),
            ("bench.sample", 60 * MS, 110 * MS),
            ("unrelated", 0, 200 * MS)]
    ops = {"/device:TPU:0": [("fusion.1", 5 * MS, 40 * MS),   # clipped to 10..40
                             ("dot.2", 30 * MS, 55 * MS),     # overlaps fusion.1
                             ("fusion.1", 70 * MS, 80 * MS),
                             ("late", 150 * MS, 160 * MS)]}   # outside the window
    s = reduce_events(ops, host)
    assert s["window_s"] == pytest.approx(0.100)
    assert s["busy_s"] == pytest.approx(0.055)  # 10..55 and 70..80
    # by self time: the 10 ms in which dot.2 overlaps fusion.1 is dot.2's
    assert dict(s["device_ops"]) == pytest.approx({"fusion.1": 0.030, "dot.2": 0.025})
    # idle: 55..60 in sync, 60..70 and 80..110 in sample
    assert dict(s["idle_gaps"]) == pytest.approx({"sample": 0.040, "sync": 0.005})


def test_reduction_averages_over_chips_and_needs_a_window():
    host = [("bench.window", 0, 100 * MS)]
    ops = {"/device:TPU:0": [("a", 0, 100 * MS)], "/device:TPU:1": [("a", 0, 50 * MS)]}
    s = reduce_events(ops, host)
    assert s["busy_s"] == pytest.approx(0.075)
    assert dict(s["idle_gaps"]) == pytest.approx({"other": 0.025})
    assert reduce_events(ops, []) is None
    assert reduce_events({"/device:TPU:0": []}, host) is None


def test_recorded_chip_trace():
    """Five runs of a matmul chain, each followed by 20 ms of host sleep in
    the ``sample`` phase (``benchmarks/chip/capture_trace.py`` on a v5e):
    the device is busy for exactly the five runs of the program, and idle
    through every sleep."""
    from jax.profiler import ProfileData

    host = json.loads((DATA / "expected.json").read_text())
    s = reduce_xplane(DATA / "trace.xplane.pb")
    data = ProfileData.from_file(str(DATA / "trace.xplane.pb"))
    runs = [ev.duration_ns / 1e9 for p in data.planes if p.name == "/device:TPU:0"
            for line in p.lines if line.name == "XLA Modules" for ev in line.events]
    assert len(runs) == 5
    assert s["busy_s"] == pytest.approx(sum(runs), rel=0.02)
    assert s["window_s"] == pytest.approx(host["window"], rel=1e-3)
    idle = dict(s["idle_gaps"])
    assert idle["sample"] == pytest.approx(host["sample"], rel=0.01)
    assert idle["sample"] == max(idle.values())
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)
    ops = dict(s["device_ops"])
    # the matmul fusion inside the loop holds nearly all of the busy time;
    # the loop itself, by self time, almost none
    assert max(ops, key=ops.get).startswith("convolution")
    assert ops["while"] < 1e-5
