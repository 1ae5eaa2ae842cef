#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip:

    python3 benchmarks/chip/readings.py --workload <name> --seeds 1 2 3 \\
        [--variant program|control|stale|halfbatch|nomix|swapped ...]

Per seed, set-up's checked block of the cell's timed path against the
reference, with no window, in one process (the programs compile once):

- ``program``: the program as the configuration states it (lower readings);
- ``control``: the program at the precision below the configuration's
  (bfloat16 for float32), the step a later change would be tempted by;
- ``stale``, ``halfbatch``, ``nomix``, ``swapped``: the timed path broken
  underneath (a block that returns its state, half of each batch left out,
  the mix left out, two agents training on each other's share of the
  data).

Each reading is judged by the harness's own comparison against the cell's
limits (``correct``).  One JSON line per reading goes to standard output
and to ``chiprun_out/bench/readings.jsonl``.  The benchmark's own runs never
run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

LOWER = {"float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variant", action="append", default=None)
    args = ap.parse_args(argv)

    from chipbench import compare, device, registry

    cell = registry.load_cell(ROOT, args.workload)
    device.enable_compile_cache(ROOT)
    try:
        device.require_tpu(cell.chips)
    except device.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 3
    out = ROOT / "chiprun_out" / "bench"
    out.mkdir(parents=True, exist_ok=True)
    for variant in args.variant or ["program"]:
        dtype = LOWER[cell.config["dtype"]] if variant == "control" else None
        fault = variant if variant in cell.runner.FAULTS else None
        if variant not in ("program", "control") and fault is None:
            ap.error(f"unknown variant {variant!r}")
        for seed in args.seeds:
            t0 = time.perf_counter()
            got = cell.runner.numbers_for(cell, seed, dtype=dtype, fault=fault)
            correct = compare.passed(compare.checks(got["numbers"], cell.limits))
            line = json.dumps({"workload": cell.name, "variant": variant, "seed": seed,
                               "correct": correct, **got,
                               "seconds": time.perf_counter() - t0})
            print(line, flush=True)
            with open(out / "readings.jsonl", "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
