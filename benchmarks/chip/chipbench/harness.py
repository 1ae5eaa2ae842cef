"""One run of one cell: find its pieces, refuse anything but the chip it
asks for, hand it to its runner, check, and print one JSON line last."""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path
from typing import Any, Optional

from chipbench import compare, device, registry


@dataclasses.dataclass
class Context:
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: Any
    on_chip: bool
    peaks: Optional[dict]
    fault: Optional[str] = None
    dtype: Optional[str] = None


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True, help="a workload name of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="any whole number")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report the per-layer metrics")
    return ap.parse_args(argv)


def fail(msg: str, code: int) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv, *, root: Path, t_start: float, on_chip: bool = True,
         fault: Optional[str] = None, dtype: Optional[str] = None) -> int:
    """``on_chip=False``, ``fault`` and ``dtype`` are for the benchmark's own
    tests: they skip the look for a chip, break the timed path underneath,
    or run the program at another precision than its configuration's."""
    args = parse(argv)
    root = Path(root)
    try:
        cell = registry.load_cell(root, args.workload)
    except (FileNotFoundError, KeyError) as e:
        return fail(f"cannot resolve workload {args.workload!r}: {e}", 2)
    src = root / "src"
    if not (src / "repro").is_dir():
        return fail(f"no program under {src}: run from a checkout of the repository", 2)
    sys.path.insert(0, str(src))

    import jax

    if on_chip:
        device.enable_compile_cache(root)
        try:
            devices = device.require_tpu(cell.chips)
            peaks = device.peaks(devices[0].device_kind)
        except (device.NoChip, KeyError) as e:
            return fail(str(e), 3)
    else:
        devices, peaks = jax.devices()[: cell.chips], None
    ctx = Context(args.seed, args.seconds, bool(args.trace), t_start, devices, on_chip,
                  peaks, fault, dtype)
    run = cell.runner.run(cell, ctx)

    checks = compare.checks(run["numbers"], cell.limits)
    correct = compare.passed(checks) and run["failed"] == 0
    metrics = registry.read_metrics(cell, run, ctx.trace)
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = run["memory_peak_bytes"]
    breakdown = None
    if ctx.trace and run["trace"] is not None:
        s = run["trace"]
        dev["busy_s"], dev["window_s"] = s["busy_s"], s["window_s"]
        breakdown = {"device_ops": s["device_ops"], "idle_gaps": s["idle_gaps"]}
    print(f"chipbench: {cell.name} seed {args.seed}: setup {run['setup_s']!r} s, window "
          f"{run['window_s']!r} s, check {run['check_s']!r} s, {run['attempted']} attempted, "
          f"{run['failed']} failed",
          file=sys.stderr)
    compare.print_checks(checks)
    print(compare.result_line(correct, run["attempted"], run["failed"], metrics, dev,
                              checks, breakdown), flush=True)
    return 0
