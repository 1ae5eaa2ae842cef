"""Find a cell's pieces by name: nothing here lists a configuration, a
traffic mix, a runner or a metric.

``BENCHMARK.json`` at the root names each cell's configuration and traffic
mix and each metric.  Every piece is a file of its own, looked up under each
directory of ``paths`` in turn and then under this benchmark's own
directory:

- ``configs/<config>.json`` (the ``file`` of the configuration's entry),
- ``traffic/<traffic>.json``: the mix's parameters; its ``runner`` names
  ``runners/<runner>.py``,
- ``families/<family>.py`` and ``reference/<family>.py``: how the program
  builds a model of that family, and the plain reference beside it,
- ``metrics/<metric>.py``: a reader ``read(run) -> float | None``,
- ``limits/<workload>.json``: the limit of each number the check compares.

So a later change adds a cell, a mix or a metric by adding files and
entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads``, with every piece it names resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    search: List[Path]

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(self.search, kind, name)

    @property
    def family(self) -> ModuleType:
        return self.module("families", self.config["family"])

    @property
    def reference(self) -> ModuleType:
        return self.module("reference", self.config["family"])

    @property
    def runner(self) -> ModuleType:
        return self.module("runners", self.traffic["runner"])

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def search_dirs(root: Path, bench: dict) -> List[Path]:
    dirs = [root / p for p in bench.get("paths", [])]
    return dirs + [HERE] if HERE not in dirs else dirs


def find_file(search: List[Path], kind: str, name: str, suffix: str) -> Path:
    for d in search:
        path = d / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind}/{name}{suffix} under {[str(d) for d in search]}")


def load_module(search: List[Path], kind: str, name: str) -> ModuleType:
    """Import ``<kind>/<name>.py`` (names may hold dots and dashes)."""
    path = find_file(search, kind, name, ".py").resolve()
    # one module per file: two roots may hold different files of one name
    key = "chipbench_" + "".join(c if c.isalnum() else "_" for c in str(path))
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str, bench: Optional[dict] = None) -> Cell:
    root = Path(root)
    if bench is None:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    search = search_dirs(root, bench)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    limits_path = find_file(search, "limits", name, ".json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(find_file(search, "traffic", w["traffic"], ".json").read_text()),
        limits=json.loads(limits_path.read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        search=search,
    )


def read_metrics(cell: Cell, run: dict, trace: bool) -> Dict[str, dict]:
    """Each metric of the cell that its reader finds something to read."""
    out = {}
    for m in cell.metrics(trace):
        value = cell.module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
