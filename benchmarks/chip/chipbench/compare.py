"""The comparison that decides ``correct``: the numbers it compares, each
against a limit of its own, and how they are printed."""
from __future__ import annotations

import json
import math
import statistics
import sys
from typing import Dict, List, Sequence

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone; its change is not compared
ZERO_GRAD_SHARE = 1e-3


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Widest relative gap between the program's and the reference's loss of
    each checked step."""
    if len(prog) != len(ref):
        return math.inf
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], leaves=None) -> Dict[str, float]:
    """Each leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    leaves = sorted(ref) if leaves is None else leaves
    if set(prog) != set(ref):
        return {"(leaves differ)": math.inf}
    med = statistics.median(ref[k] for k in ref)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], leaves=None) -> float:
    """The worst leaf's gap."""
    gaps = leaf_gaps(prog, ref, leaves)
    return max(gaps.values()) if gaps else math.inf


def moving_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is more than round-off."""
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v >= ZERO_GRAD_SHARE * med)


def row_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray], leaves=None) -> float:
    """The worst agent's gap: for each leaf and agent, the gap between the
    program's and the reference's norm of that agent's row, against the
    reference's norm of that row or the median leaf's median row norm,
    whichever is larger.  A fault in a few agents of a large fleet, which
    the norm over the whole stack dilutes, shows here undiluted."""
    leaves = sorted(ref) if leaves is None else leaves
    if set(prog) != set(ref) or any(np.shape(prog[k]) != np.shape(ref[k]) for k in ref):
        return math.inf
    med = float(np.median([np.median(ref[k]) for k in ref]))
    gaps = [np.max(np.abs(prog[k] - ref[k]) / np.maximum(ref[k], med)) for k in leaves]
    return float(max(gaps)) if gaps else math.inf


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``loss_gap`` over the checked rounds; ``grad_gap`` on the gradient the
    state holds after them and ``step_gap`` on the parameters' change, each
    by the worst leaf; ``grad_gap_agent`` and ``step_gap_agent`` the same
    by the worst agent's row of any leaf."""
    moving = moving_leaves(ref["g_norm"])
    return {
        "loss_gap": loss_gap(prog["loss"], ref["loss"]),
        "grad_gap": norm_gap(prog["g_norm"], ref["g_norm"]),
        "step_gap": norm_gap(prog["dx_norm"], ref["dx_norm"], moving),
        "grad_gap_agent": row_gap(prog["g_rows"], ref["g_rows"]),
        "step_gap_agent": row_gap(prog["dx_rows"], ref["dx_rows"], moving),
    }


def worst_leaves(prog: dict, ref: dict) -> Dict[str, str]:
    """Which leaf sets ``grad_gap`` and ``step_gap``: for the look at a
    number that reads high."""
    out = {}
    for name, key, leaves in (("grad_gap", "g_norm", None),
                              ("step_gap", "dx_norm", moving_leaves(ref["g_norm"]))):
        gaps = leaf_gaps(prog[key], ref[key], leaves)
        out[name] = max(gaps, key=gaps.get) if gaps else ""
    return out


def checks(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit; a number without a limit, or a
    limit without a number, is an error of the benchmark."""
    if set(numbers) != set(limits):
        raise KeyError(f"numbers {sorted(numbers)} and limits {sorted(limits)} differ")
    return {k: {"value": float(numbers[k]), "limit": float(limits[k])} for k in sorted(numbers)}


def passed(result: Dict[str, dict]) -> bool:
    # NaN compares false: a number that is not finite fails
    return all(c["value"] <= c["limit"] for c in result.values())


def print_checks(result: Dict[str, dict], file=sys.stderr) -> None:
    for k, c in result.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} {verdict}", file=file)
    file.flush()


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
                checks_: Dict[str, dict], breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks_
    return json.dumps(out)
