"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True only when JAX's backend is the CPU; on a TPU
the kernels always compile through Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import gt_update as _gt
from repro.kernels import quantize as _qz
from repro.kernels import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    block_q: int = 128, block_k: int = 128, interpret: Optional[bool] = None,
):
    """q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D) -> (B,Hq,Sq,D)."""
    interp = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interp,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x, dt, a, b_mat, c_mat, *, chunk: int = 128, interpret: Optional[bool] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD over (B,L,H,P) with state (B,H,P,N); returns (y, final_state)."""
    interp = _default_interpret() if interpret is None else interpret
    return _ssd.ssd_scan_kernel(
        x, dt, a, b_mat, c_mat, chunk=chunk, interpret=interp
    )


@functools.partial(jax.jit, static_argnames=("eta_l", "interpret"))
def fused_local_step(x, y, g_new, g_old, *, eta_l: float, interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _gt.fused_local_step(x, y, g_new, g_old, eta_l, interpret=interp)


@functools.partial(
    jax.jit,
    static_argnames=("eta_c", "eta_l", "w_self", "w_left", "w_right", "interpret"),
)
def fused_mix_combine(
    x_k, x_to, y_to, left, right, *,
    eta_c: float, eta_l: float, w_self: float, w_left: float, w_right: float,
    interpret: Optional[bool] = None,
):
    interp = _default_interpret() if interpret is None else interpret
    return _gt.fused_mix_combine(
        x_k, x_to, y_to, left, right,
        eta_c=eta_c, eta_l=eta_l,
        w_self=w_self, w_left=w_left, w_right=w_right,
        interpret=interp,
    )


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def rowwise_quant_dequant(x, *, bits: int = 8, interpret: Optional[bool] = None):
    """Per-agent-row int quantizer round trip over (n_agents, d)."""
    interp = _default_interpret() if interpret is None else interpret
    return _qz.rowwise_quant_dequant(x, bits=bits, interpret=interp)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def fused_compressed_mix(x, w, *, bits: int = 8, interpret: Optional[bool] = None):
    """Fused quantize → mix → dequantize:  x + W·q(x) − q(x)."""
    interp = _default_interpret() if interpret is None else interpret
    return _qz.fused_compressed_mix(x, w, bits=bits, interpret=interp)
