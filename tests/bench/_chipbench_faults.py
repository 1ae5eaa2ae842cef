"""Drive whole runs of a smoke-size cell off the chip, sound, with the timed
path broken, or at a lower precision, and read ``correct`` from the result
line."""
from __future__ import annotations

import contextlib
import io
import json
import time

import _chipbench_tiny as tiny
from chipbench import harness


def run_line(root, workload: str, fault=None, dtype=None, seed: int = 5_000_000_017) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
                           "--trace", "0"], root=root, t_start=time.perf_counter(),
                          on_chip=False, fault=fault, dtype=dtype)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


__all__ = ["run_line", "tiny"]
