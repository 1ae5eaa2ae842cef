"""A benchmark root in a temporary directory with smoke-size cells, for the
benchmark's tests on the CPU: its own BENCHMARK.json, configuration, traffic
and limit files, and the repository's program under ``src``."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_MLP = {"family": "mlp", "d_in": 784, "hidden": 32, "n_classes": 10,
            "activation": "sigmoid", "dtype": "float32", "matmul_precision": "highest"}


def traffic_like(real_traffic: str, **changes) -> dict:
    t = json.loads((BENCH / "traffic" / f"{real_traffic}.json").read_text())
    t.update(changes)
    return t


CELLS = {
    # name: (config name, config, traffic name, traffic, the real cell whose limits it keeps)
    "tiny.fleet": ("tiny-mlp", TINY_MLP, "tiny-ring16",
                   traffic_like("ring4096", agents=16, samples_per_agent=8, batch=4),
                   "fleet.paper-mlp.ring4096"),
}


def make_root(tmp: Path, cells=CELLS, extra_metrics=()) -> Path:
    """Write the root; ``extra_metrics`` are per-layer entries to add."""
    tmp = Path(tmp)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    own = tmp / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (own / sub).mkdir(parents=True, exist_ok=True)
    bench["paths"] = ["bench"]
    bench["configs"], bench["workloads"] = [], []
    for name, (cname, cfg, tname, traffic, real) in cells.items():
        (own / "configs" / f"{cname}.json").write_text(json.dumps(cfg))
        (own / "traffic" / f"{tname}.json").write_text(json.dumps(traffic))
        shutil.copy(BENCH / "limits" / f"{real}.json", own / "limits" / f"{name}.json")
        bench["configs"].append({"name": cname, "source": "test", "file": f"bench/configs/{cname}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": name, "config": cname, "traffic": tname,
                                   "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(name)
    bench["per_layer"] += list(extra_metrics)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "src").symlink_to(REPO / "src")
    return tmp
