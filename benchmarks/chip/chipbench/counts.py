"""Operations and bytes that the work of a round needs, from shapes alone.

These are the yardstick's counts: they depend on the configuration and the
traffic, never on how the program computes, so a change to the program
cannot move them.  Each function states what it counts.
"""
from __future__ import annotations


def mlp_train_flops_per_sample(cfg: dict) -> float:
    """FLOPs of one sample's forward and backward pass through the
    ``d_in - hidden - classes`` MLP: forward ``2 (d_in h + h c)``, weight
    gradients the same again, and the gradient into the hidden layer
    ``2 h c`` (none into the input, which is data)."""
    a = cfg["d_in"] * cfg["hidden"]
    b = cfg["hidden"] * cfg["n_classes"]
    return 2.0 * (a + b) * 2 + 2.0 * b


def mlp_param_count(cfg: dict) -> int:
    return cfg["d_in"] * cfg["hidden"] + cfg["hidden"] + cfg["hidden"] * cfg["n_classes"] + cfg["n_classes"]


def pisco_round_state_bytes(n_agents: int, param_bytes: int) -> float:
    """Least HBM traffic of PISCO's state in one round: every agent's x, y
    and g is changed by the round, so each is read once and written once,
    however the round is fused."""
    return 6.0 * n_agents * param_bytes


def round_batch_bytes(n_agents: int, t_o: int, batch: int, sample_bytes: int) -> float:
    """Each of the round's ``t_o + 1`` minibatches of every agent is read
    once."""
    return float((t_o + 1) * n_agents * batch * sample_bytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time on one chip: the larger of operations over peak FLOP/s and
    bytes over peak HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
