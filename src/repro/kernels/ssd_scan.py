"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid ``(batch, heads, num_chunks)`` with the chunk axis innermost and
sequential: the inter-chunk SSM state ``(P, N)`` lives in fp32 VMEM scratch
and is carried across chunk steps — the TPU-native replacement for the
paper's GPU kernel, trading warp-level parallel prefix for the systolic
strengths of the MXU (the per-chunk work is 4 small matmuls on
(chunk × chunk/N/P)-shaped operands, all VMEM-resident).

Per chunk c with decays  a_t = dt_t · A_h  (negative):
  cum_t   = cumsum(a)                (within chunk)
  S_{ls}  = exp(cum_l - cum_s)·dt_s  for l >= s          (decay matrix)
  y_diag  = ((C Bᵀ) ⊙ S) x
  y_off   = exp(cum)_l · (C h_inᵀ)
  h_out   = exp(cum_L) h_in + Σ_s dt_s·exp(cum_L - cum_s)·x_s ⊗ B_s

Validated in interpret mode against :func:`repro.kernels.ref.ssd_scan_ref`
(the direct O(L) recurrence) and the chunked jnp reference in
``repro.models.mamba2``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    x_ref,  # (1, 1, cl, P)
    dt_ref,  # (1, 1, 1, cl)
    adt_ref,  # (1, 1, 1, cl)  dt · A_h
    b_ref,  # (1, 1, cl, N)
    c_ref,  # (1, 1, cl, N)
    y_ref,  # (1, 1, cl, P)
    hfin_ref,  # (1, 1, P, N)
    h_scr,  # (P, N) fp32 carried state
    *,
    cl: int,
    nc: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (cl, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (1, cl)
    a_dt = adt_ref[0, 0].astype(jnp.float32)  # (1, cl) negative
    b = b_ref[0, 0].astype(jnp.float32)  # (cl, N)
    c = c_ref[0, 0].astype(jnp.float32)  # (cl, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    causal = li >= si
    # cumsum and the row -> column moves as masked lane reductions and a
    # square transpose (no 1-D vectors, which Mosaic cannot lay out)
    cum = jnp.sum(jnp.where(causal, a_dt, 0.0), axis=1, keepdims=True)  # (cl, 1)
    dt_col = jnp.sum(jnp.where(li == si, dt, 0.0), axis=1, keepdims=True)
    total = jnp.sum(a_dt, axis=1, keepdims=True)  # (1, 1) = cum at chunk end

    # decay matrix S[l, s] = exp(cum_l - cum_s) * dt_s   (l >= s)
    cum_ls = jnp.broadcast_to(cum, (cl, cl))
    seg = jnp.where(causal, cum_ls - cum_ls.T, NEG_INF)
    s_mat = jnp.exp(seg) * dt

    h_in = h_scr[...]  # (P, N)
    nt = (((1,), (1,)), ((), ()))  # contract the last dims: A @ B.T

    scores = jax.lax.dot_general(c, b, nt) * s_mat  # (cl, cl)
    y_diag = scores @ x  # (cl, P)
    y_off = jnp.exp(cum) * jax.lax.dot_general(c, h_in, nt)  # (cl, P)
    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update to the chunk boundary
    w = dt_col * jnp.exp(total - cum)  # (cl, 1)
    tn = (((0,), (0,)), ((), ()))  # contract the first dims: A.T @ B
    h_new = jnp.exp(total) * h_in + jax.lax.dot_general(x * w, b, tn)  # (P, N)
    h_scr[...] = h_new

    @pl.when(ci == nc - 1)
    def _finish():
        hfin_ref[0, 0] = h_new


def ssd_scan_kernel(
    x: jnp.ndarray,  # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H)
    a: jnp.ndarray,  # (H,)
    b_mat: jnp.ndarray,  # (B, L, G, N)
    c_mat: jnp.ndarray,  # (B, L, G, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2:]
    assert h % g == 0
    group = h // g
    cl = min(chunk, l)
    assert l % cl == 0, f"seq {l} must divide chunk {cl}"
    nc = l // cl

    # head-major layout: every block's last two dims are a (sequence, lane)
    # tile, or the per-head row of dt over the sequence
    x_h = jnp.moveaxis(x, 2, 1)  # (B, H, L, P)
    dt_h = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)[:, :, None, :]  # (B, H, 1, L)
    adt_h = dt_h * a.astype(jnp.float32)[None, :, None, None]
    b_h = jnp.moveaxis(b_mat, 2, 1)  # (B, G, L, N)
    c_h = jnp.moveaxis(c_mat, 2, 1)

    kernel = functools.partial(_ssd_kernel, cl=cl, nc=nc)
    seq_row = pl.BlockSpec((1, 1, 1, cl), lambda bi, hi, ci: (bi, hi, 0, ci))
    grouped = pl.BlockSpec((1, 1, cl, n), lambda bi, hi, ci: (bi, hi // group, ci, 0))
    y, hfin = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, cl, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            seq_row,
            seq_row,
            grouped,
            grouped,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x_h, dt_h, adt_h, b_h, c_h)
    return jnp.moveaxis(y, 1, 2), hfin
