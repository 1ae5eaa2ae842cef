"""Arithmetic shared by the metric readers in ``metrics/``.

A reader takes the run's record (see ``runners/train.py:run``) and returns a
number, or ``None`` where the run holds nothing to read: then the metric is
left out of the result line, never reported as 0.
"""
from __future__ import annotations

from typing import Optional

from chipbench import counts


def per_round_ms(run: dict, phase: str) -> Optional[float]:
    """Host milliseconds per round spent in one of the harness's phases."""
    if not run["rounds"] or phase not in run["phase_s"]:
        return None
    return 1000.0 * run["phase_s"][phase] / run["rounds"]


def idle_share(run: dict) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the device."""
    s = run["trace"]
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def roofline_share(run: dict) -> Optional[float]:
    """Least time of the rounds completed in the traced window (the larger
    of FLOPs over peak and bytes over HBM bandwidth, per round), over the
    window's length, in percent."""
    s, flops, nbytes = run["trace"], run["flops_per_round"], run["bytes_per_round"]
    if not s or flops is None or nbytes is None or not run["peaks"]:
        return None
    least = counts.roofline_seconds(flops, nbytes, run["peaks"])
    return 100.0 * run["rounds"] * least / s["window_s"]
