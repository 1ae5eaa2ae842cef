"""Flash attention as a Pallas TPU kernel (causal / sliding-window / GQA).

TPU-native design (vs. the CUDA original): the grid is
``(batch, q_heads, num_q_blocks, num_k_blocks)`` with the key-block axis
*innermost and sequential* — TPU grids execute in order on each core, so the
online-softmax running statistics (m, l, acc) live in VMEM scratch that
persists across the k-block steps of one q block.  Block sizes default to
(128, 128): MXU-aligned on the contraction dims.  GQA is handled in the
BlockSpec index maps (query head h reads kv head ``h // group``) so no
repeated K/V ever materializes in HBM.

Validated in interpret mode against :func:`repro.kernels.ref.flash_attention_ref`.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bk, d)
    v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, 1, bq, d)
    m_scr,  # (bq, 1) running max
    l_scr,  # (bq, 1) running denom
    acc_scr,  # (bq, d) running numerator
    *,
    scale: float,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
    window: Optional[int],
):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = (q @ k.T) * scale  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    # row statistics stay (bq, 1) columns: Mosaic has no layout for a 1-D
    # vector reshaped against the (bq, bk) tile
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # guard fully-masked rows (all NEG_INF): exp(NEG_INF - NEG_INF) = 1 junk
    row_live = m_new > NEG_INF / 2
    p = jnp.where(row_live, p, 0.0)
    alpha = jnp.where(row_live, jnp.exp(m_prev - m_new), 1.0)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + p @ v
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hkv, Sk, D)
    v: jnp.ndarray,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, "seq lengths must divide block sizes"
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        bq=bq,
        bk=bk,
        nk=nk,
        causal=causal,
        window=window,
    )
    grid = (b, hq, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
