"""Fused sparse-gossip Pallas kernels: scatter-accumulate over edge blocks.

The sparse mixing path (repro.core.mixing.sparse_mixing) evaluates, per leaf,

    out_i = self_w[i] * x_i + sum_{e : senders[e] -> i} edge_w[e] * x_send

and its compressed form dequant -> scatter-accumulate -> combine,

    q   = dequant(quant(x))            per-agent-row symmetric int grid
    out = x + gamma * (W q - q)        mean-preserving difference gossip

where the implicit ``W q`` is the same per-edge gather/scatter.  Unfused the
compressed form round-trips the quantized payload through HBM; the kernels
here do one pass per column block, accumulating edge contributions across a
second (innermost) grid axis into a VMEM-resident output block.

Tiling follows quantize.py: lane-aligned ``(rows, 128·c)`` tiles with padded
tails, per-row quantization scales computed by the shared two-phase
max-reduction.  Edge arrays are padded to an EDGE_BLOCK multiple with weight-0
sentinel edges (sender = receiver = 0), which contribute exactly nothing.
Rounding is deterministic round-to-nearest — bit-matching ``kernels/ref.py``
and the ``stochastic=False`` compressor.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.quantize import (
    COL_BLOCK,
    LANE,
    _pad2d,
    _qmax,
    _row_scales,
)

EDGE_BLOCK = 512  # directed edges processed per grid step


def _pad_edges(
    senders: jnp.ndarray, receivers: jnp.ndarray, edge_w: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Pad directed-edge arrays to an EDGE_BLOCK multiple and reshape to
    (n_blocks, 1, EDGE_BLOCK) so a BlockSpec can slice one block per grid
    step (the block's last two dims, (1, EDGE_BLOCK), are TPU-tileable).
    Padding edges carry weight 0 into row 0 — a no-op contribution."""
    e = int(senders.shape[0])
    ep = max(EDGE_BLOCK, -(-e // EDGE_BLOCK) * EDGE_BLOCK)
    pad = ep - e
    if pad:
        senders = jnp.pad(senders, (0, pad))
        receivers = jnp.pad(receivers, (0, pad))
        edge_w = jnp.pad(edge_w, (0, pad))
    nb = ep // EDGE_BLOCK
    return (
        senders.reshape(nb, 1, EDGE_BLOCK),
        receivers.reshape(nb, 1, EDGE_BLOCK),
        edge_w.reshape(nb, 1, EDGE_BLOCK),
        nb,
    )


def _sparse_mix_kernel(x_ref, send_ref, recv_ref, ew_ref, sw_ref, o_ref):
    e = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = sw_ref[:, :1].astype(jnp.float32) * x

    send = send_ref[0, 0]
    recv = recv_ref[0, 0]
    w = ew_ref[0, 0].astype(jnp.float32)
    contrib = w[:, None] * x[send]
    o_ref[...] += jnp.zeros_like(x).at[recv].add(contrib)


def _sparse_compressed_mix_kernel(
    x_ref, send_ref, recv_ref, ew_ref, sw_ref, s_ref, o_ref, *, qmax, gamma
):
    e = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(s_ref[:, :1].astype(jnp.float32), 1e-12) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale

    @pl.when(e == 0)
    def _init():
        sw = sw_ref[:, :1].astype(jnp.float32)
        o_ref[...] = x + gamma * (sw - 1.0) * q

    send = send_ref[0, 0]
    recv = recv_ref[0, 0]
    w = ew_ref[0, 0].astype(jnp.float32)
    contrib = w[:, None] * q[send]
    o_ref[...] += gamma * jnp.zeros_like(x).at[recv].add(contrib)


def _prep(x: jnp.ndarray):
    """Lane/sublane-pad ``x`` and pick the column block size."""
    xp, n, d = _pad2d(x, LANE)
    cb = min(COL_BLOCK, xp.shape[1])
    xp, _, _ = _pad2d(xp, cb)
    return xp, n, d, cb


def _sw2d(self_w: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(rows, LANE) tile holding the per-row self weight in every lane
    (padded rows hold 0 — their x rows are zero anyway)."""
    sw = jnp.zeros(rows, jnp.float32).at[: self_w.shape[0]].set(
        self_w.astype(jnp.float32)
    )
    return jnp.broadcast_to(sw[:, None], (rows, LANE))


def sparse_mix(
    x: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_w: jnp.ndarray,
    self_w: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Edge-list gossip ``out_i = self_w_i x_i + sum_e w_e x_send`` fused in
    one pass per column block.

    ``x`` is (n_agents, d); ``senders``/``receivers``/``edge_w`` are the
    directed edge arrays (both orientations of each undirected edge).
    Matches ``kernels.ref.sparse_mix_ref`` to fp32 exactness.
    """
    n_total, _ = x.shape
    xp, n, d, cb = _prep(x)
    rows, dp = xp.shape
    send_b, recv_b, ew_b, nb = _pad_edges(
        jnp.asarray(senders, jnp.int32),
        jnp.asarray(receivers, jnp.int32),
        jnp.asarray(edge_w, jnp.float32),
    )
    sw = _sw2d(self_w, rows)
    out = pl.pallas_call(
        _sparse_mix_kernel,
        grid=(dp // cb, nb),
        in_specs=[
            pl.BlockSpec((rows, cb), lambda j, e: (0, j)),
            pl.BlockSpec((1, 1, EDGE_BLOCK), lambda j, e: (e, 0, 0)),
            pl.BlockSpec((1, 1, EDGE_BLOCK), lambda j, e: (e, 0, 0)),
            pl.BlockSpec((1, 1, EDGE_BLOCK), lambda j, e: (e, 0, 0)),
            pl.BlockSpec((rows, LANE), lambda j, e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, cb), lambda j, e: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, dp), jnp.float32),
        interpret=interpret,
    )(xp, send_b, recv_b, ew_b, sw)
    return out[:n, :d].astype(x.dtype)


def sparse_compressed_mix(
    x: jnp.ndarray,
    senders: jnp.ndarray,
    receivers: jnp.ndarray,
    edge_w: jnp.ndarray,
    self_w: jnp.ndarray,
    *,
    bits: int = 8,
    gamma: float = 1.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """One-pass ``x + gamma (W q(x) - q(x))`` over an edge list: per-row
    int-``bits`` dequant, per-edge-block scatter-accumulate, difference
    combine — the quantized payload never round-trips through HBM.

    Matches ``kernels.ref.sparse_compressed_mix_ref`` to fp32 exactness.
    """
    qm = _qmax(bits)
    xp, n, d, cb = _prep(x)
    rows, dp = xp.shape
    send_b, recv_b, ew_b, nb = _pad_edges(
        jnp.asarray(senders, jnp.int32),
        jnp.asarray(receivers, jnp.int32),
        jnp.asarray(edge_w, jnp.float32),
    )
    sw = _sw2d(self_w, rows)
    scales = _row_scales(xp, cb, interpret)
    out = pl.pallas_call(
        functools.partial(_sparse_compressed_mix_kernel, qmax=qm, gamma=gamma),
        grid=(dp // cb, nb),
        in_specs=[
            pl.BlockSpec((rows, cb), lambda j, e: (0, j)),
            pl.BlockSpec((1, 1, EDGE_BLOCK), lambda j, e: (e, 0, 0)),
            pl.BlockSpec((1, 1, EDGE_BLOCK), lambda j, e: (e, 0, 0)),
            pl.BlockSpec((1, 1, EDGE_BLOCK), lambda j, e: (e, 0, 0)),
            pl.BlockSpec((rows, LANE), lambda j, e: (0, 0)),
            pl.BlockSpec((rows, LANE), lambda j, e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, cb), lambda j, e: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, dp), jnp.float32),
        interpret=interpret,
    )(xp, send_b, recv_b, ew_b, sw, scales)
    return out[:n, :d].astype(x.dtype)


def topology_edge_arrays(topo) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Directed ``(senders, receivers, edge_w)`` for a SparseTopology —
    convenience for feeding :func:`sparse_mix` straight from a topology."""
    e = topo.edges
    if len(e) == 0:
        z = np.zeros(0, dtype=np.int32)
        return z, z.copy(), np.zeros(0, dtype=np.float32)
    senders = np.concatenate([e[:, 0], e[:, 1]]).astype(np.int32)
    receivers = np.concatenate([e[:, 1], e[:, 0]]).astype(np.int32)
    edge_w = np.concatenate([topo.edge_weight, topo.edge_weight]).astype(
        np.float32
    )
    return senders, receivers, edge_w
