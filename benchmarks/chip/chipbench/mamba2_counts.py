"""Operations and bytes of a Mamba-2 language model's training round, from
shapes alone (arXiv:2405.21060).

Like :mod:`chipbench.counts`, these are the yardstick's counts: they read
the configuration (``configs/<config>.json``: widths ``d_model``,
``d_inner``, ``n_heads``, ``head_dim``, ``d_state``, ``n_groups``,
``d_conv``, ``chunk``, ``vocab_size``, depth ``n_layers``) and the traffic
(``agents``, ``t_o``, ``batch``, ``seq``), never how the program computes.
A multiply-add is two operations.  Training is three times the forward
pass: each product of the forward pass has two of the same size in the
backward pass; recomputation is not counted.  Sequences are taken as a
whole number of chunks (the traffic's are).
"""
from __future__ import annotations

from chipbench import counts


def _conv_channels(cfg: dict) -> int:
    return cfg["d_inner"] + 2 * cfg["n_groups"] * cfg["d_state"]


def _in_proj_width(cfg: dict) -> int:
    """z, x, B, C and dt: ``2 d_inner + 2 G N + H``."""
    return cfg["d_inner"] + _conv_channels(cfg) + cfg["n_heads"]


def layer_param_count(cfg: dict) -> int:
    """One block: its pre-norm, in_proj, the depthwise conv's weight and
    bias, A_log, dt_bias and D per head, the gated norm, out_proj."""
    d, di, h = cfg["d_model"], cfg["d_inner"], cfg["n_heads"]
    conv = _conv_channels(cfg)
    return d + d * _in_proj_width(cfg) + (cfg["d_conv"] + 1) * conv + 3 * h + di + di * d


def param_count(cfg: dict) -> int:
    """The tied embedding, the blocks and the final norm."""
    return (cfg["vocab_size"] * cfg["d_model"] + cfg["n_layers"] * layer_param_count(cfg)
            + cfg["d_model"])


def ssd_forward_flops_per_token(cfg: dict) -> float:
    """The SSD's forward work per token and layer, chunk length Q, H heads
    of P, state N, G groups: the intra-chunk ``C B^T`` per group
    (``2 G Q N``), the decay mask applied and the masked product with X
    (``H Q (2 P + 1)``), the chunk states ``B^T (decay * X)`` and the
    state-to-output step ``C h`` (``2 H N P`` each), and the state passing
    between chunks (``2 H N P`` per chunk, so ``2 H N P / Q`` per token)."""
    q, n, g = cfg["chunk"], cfg["d_state"], cfg["n_groups"]
    h, p = cfg["n_heads"], cfg["head_dim"]
    return 2.0 * g * q * n + h * q * (2 * p + 1) + 4.0 * h * n * p + 2.0 * h * n * p / q


def ssd_bytes_per_token(cfg: dict) -> float:
    """Least HBM traffic of the SSD per token and layer in training, float32:
    the forward pass reads x (``H P``), dt (``H``), B and C (``2 G N``) and
    writes y (``H P``); the backward pass reads dy and the same inputs and
    writes their gradients.  So three times the inputs and twice y."""
    h, p = cfg["n_heads"], cfg["head_dim"]
    inputs = h * p + h + 2 * cfg["n_groups"] * cfg["d_state"]
    return 4.0 * (3 * inputs + 2 * h * p)


def layer_forward_flops_per_token(cfg: dict) -> float:
    """One block's forward work per token: in_proj, the depthwise conv
    (``2 d_conv`` per channel), the SSD and out_proj.  Norms, gates and
    activations are left out."""
    d, di = cfg["d_model"], cfg["d_inner"]
    return (2.0 * d * _in_proj_width(cfg) + 2.0 * cfg["d_conv"] * _conv_channels(cfg)
            + ssd_forward_flops_per_token(cfg) + 2.0 * di * d)


def sequences_per_round(traffic: dict) -> int:
    """Every agent's ``T_o + 1`` minibatches of ``batch`` sequences."""
    return traffic["agents"] * (traffic["t_o"] + 1) * traffic["batch"]


def train_flops_per_round(cfg: dict, traffic: dict) -> float:
    """Three times the forward pass of every sequence of the round: each
    of its ``seq`` tokens through ``n_layers`` blocks, and the tied head's
    ``2 d_model V`` at the ``seq - 1`` positions that predict a token."""
    seqs, seq = sequences_per_round(traffic), traffic["seq"]
    blocks = seqs * seq * cfg["n_layers"] * layer_forward_flops_per_token(cfg)
    head = seqs * (seq - 1) * 2.0 * cfg["d_model"] * cfg["vocab_size"]
    return 3.0 * (blocks + head)


def train_bytes_per_round(cfg: dict, traffic: dict) -> float:
    """PISCO's float32 state read and written once, and the round's token
    windows (int32 tokens and the stream id) read once."""
    a = traffic["agents"]
    return (counts.pisco_round_state_bytes(a, 4 * param_count(cfg))
            + counts.round_batch_bytes(a, traffic["t_o"], traffic["batch"],
                                       4 * traffic["seq"] + 4))


def ssd_flops_per_round(cfg: dict, traffic: dict) -> float:
    return (3.0 * sequences_per_round(traffic) * traffic["seq"] * cfg["n_layers"]
            * ssd_forward_flops_per_token(cfg))


def ssd_bytes_per_round(cfg: dict, traffic: dict) -> float:
    return (sequences_per_round(traffic) * traffic["seq"] * cfg["n_layers"]
            * ssd_bytes_per_token(cfg))
