"""``BENCHMARK.json`` against the benchmark's contract, and the harness
finding pieces it has never seen by their names alone."""
import json
import re

import pytest

import _chipbench_tiny as tiny
from chipbench import registry

BENCH = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for kind, keys in KEYS.items():
        for entry in BENCH[kind]:
            extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
            assert keys <= set(entry) <= keys | extra, entry
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        cell = registry.load_cell(tiny.REPO, w["name"], BENCH)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert set(cell.limits) == {"loss_gap", "grad_gap", "step_gap", "grad_gap_agent",
                                    "step_gap_agent"}
        for m in cell.end_to_end + cell.per_layer:
            assert hasattr(cell.module("metrics", m["name"]), "read")
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["name"] in {w["config"] for w in BENCH["workloads"]}


def test_a_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """Files added in a directory of ``paths`` and entries in
    ``BENCHMARK.json``, with no edit to any file of the harness."""
    reader = {"name": "rounds_seen.tiny", "unit": "rounds", "better": "higher",
              "source": "host_clock", "layer": "test", "moves": "fleet_rounds_per_s",
              "workloads": ["tiny.fleet"]}
    root = tiny.make_root(tmp_path, extra_metrics=[reader])
    (root / "bench" / "metrics" / "rounds_seen.tiny.py").write_text(
        "def read(run):\n    return run['rounds'] or None\n")
    cell = registry.load_cell(root, "tiny.fleet")
    assert cell.config == tiny.TINY_MLP
    assert cell.traffic["agents"] == 16
    assert cell.family.__file__ == str(tiny.BENCH / "families" / "mlp.py")  # the harness's own
    run = {"rounds": 12, "phase_s": {}, "trace": None, "flops_per_round": 1.0,
           "bytes_per_round": 1.0, "peaks": None}
    got = registry.read_metrics(cell, run, trace=True)
    assert got == {"rounds_seen.tiny": {"value": 12.0, "unit": "rounds"}}
    # a reader that finds nothing leaves its metric out
    assert registry.read_metrics(cell, dict(run, rounds=0), trace=True) == {}
    with pytest.raises(KeyError):
        registry.load_cell(root, "no.such.cell")
