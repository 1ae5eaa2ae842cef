#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it asks for:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cells; each one's
configuration, traffic, runner, metric readers and limits are files under
this directory (see ``chipbench/registry.py``).  The run makes its weights
and inputs from ``--seed``, warms up every shape it uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
It exits non-zero, and prints no result, without a TPU.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], root=HERE.parents[1], t_start=T_START))
