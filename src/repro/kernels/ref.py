"""Pure-jnp oracles for every Pallas kernel (the allclose references).

These are deliberately naive/direct implementations — clarity over speed —
used by tests/test_kernels.py to validate the kernels across shape/dtype
sweeps in interpret mode.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hkv, Sk, D)
    v: jnp.ndarray,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kf = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / math.sqrt(d)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)


def ssd_scan_ref(
    x: jnp.ndarray,  # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H) positive
    a: jnp.ndarray,  # (H,) negative
    b_mat: jnp.ndarray,  # (B, L, G, N)
    c_mat: jnp.ndarray,  # (B, L, G, N)
    h0: Optional[jnp.ndarray] = None,  # (B, H, P, N)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Direct O(L) recurrence — the semantic ground truth of SSD."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2:]
    rep = h // g
    state = (
        jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    )

    def step(carry, t_in):
        x_t, dt_t, b_t, c_t = t_in  # (B,H,P), (B,H), (B,G,N), (B,G,N)
        b_h = jnp.repeat(b_t, rep, axis=1)
        c_h = jnp.repeat(c_t, rep, axis=1)
        decay = jnp.exp(dt_t * a[None, :])
        upd = (dt_t[..., None] * x_t)[..., :, None] * b_h[:, :, None, :]
        new = carry * decay[..., None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", new, c_h)
        return new, y

    final, ys = jax.lax.scan(
        step,
        state,
        (
            jnp.moveaxis(x.astype(jnp.float32), 1, 0),
            jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
            jnp.moveaxis(b_mat.astype(jnp.float32), 1, 0),
            jnp.moveaxis(c_mat.astype(jnp.float32), 1, 0),
        ),
    )
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def fused_local_step_ref(
    x: jnp.ndarray, y: jnp.ndarray, g_new: jnp.ndarray, g_old: jnp.ndarray, eta_l: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PISCO eq. (3a)+(3c) fused:  x' = x - eta_l*y ;  y' = y + g_new - g_old."""
    return x - eta_l * y, y + g_new - g_old


def mix_combine_ref(
    x_k: jnp.ndarray,
    x_to: jnp.ndarray,
    y_to: jnp.ndarray,
    eta_c: float,
    eta_l: float,
) -> jnp.ndarray:
    """PISCO eq. (4a) pre-mix candidate: (1-eta_c)·x_k + eta_c·(x_to - eta_l·y_to)."""
    return (1.0 - eta_c) * x_k + eta_c * (x_to - eta_l * y_to)


def neighbor_combine_ref(
    self_x: jnp.ndarray,
    left: jnp.ndarray,
    right: jnp.ndarray,
    w_self: float,
    w_left: float,
    w_right: float,
) -> jnp.ndarray:
    """Post-ppermute ring-gossip weighted combine."""
    return w_self * self_x + w_left * left + w_right * right


def rowwise_quant_dequant_ref(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per-agent-row symmetric quantizer round trip, round-to-nearest.

    x: (n_agents, d).  Identical math to the deterministic path of
    ``repro.core.compression.StochasticQuantizer``.
    """
    qmax = float(2 ** (bits - 1) - 1)
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=1, keepdims=True), 1e-12) / qmax
    q = jnp.clip(jnp.round(xf / scale), -qmax, qmax)
    return (q * scale).astype(x.dtype)


def compressed_mix_ref(x: jnp.ndarray, w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Mean-preserving compressed gossip:  x + W·q(x) − q(x)."""
    q = rowwise_quant_dequant_ref(x, bits).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    return (xf + w.astype(jnp.float32) @ q - q).astype(x.dtype)

