"""Mamba-2 block with the SSD (state-space duality) chunked algorithm
(arXiv:2405.21060), pure JAX; the Pallas kernel in
``repro.kernels.ssd_scan`` is the TPU-target equivalent of the chunked scan
and is validated against :func:`ssd_reference` below.

Layout: heads H = d_inner / head_dim(P), groups G (B/C shared per group),
state size N.  Training/prefill uses the 4-step chunked SSD; decode carries
(conv window, SSM state) caches and costs O(1) per token — the reason the
``long_500k`` shape runs for SSM/hybrid archs.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, SSMConfig
from repro.models.layers import KeyGen, normal_init, rms_norm

NEG_INF = -1e30

# Named scopes of the block's parts (``jax.named_scope``): they set only the
# ``op_name`` metadata of the HLO, so a profiler trace attributes device
# time to the input projection, the causal conv, the SSD (dt, A, the chunked
# scan and the D skip) and the gated norm with the output projection.
SCOPE_IN_PROJ = "mamba2.in_proj"
SCOPE_CONV = "mamba2.conv"
SCOPE_SSD = "mamba2.ssd"
SCOPE_OUT_PROJ = "mamba2.out_proj"


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, n_heads, conv_ch


def init_mamba2(kg: KeyGen, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    s, d_in, n_heads, conv_ch = _dims(cfg)
    d = cfg.d_model
    sc = cfg.init_scale
    proj_out = 2 * d_in + 2 * s.n_groups * s.d_state + n_heads
    key_a = kg()
    a = jax.random.uniform(
        key_a, (n_heads,), minval=s.a_init_range[0], maxval=s.a_init_range[1]
    )
    # dt bias st. softplus(dt_bias) spans [dt_min, dt_max] log-uniformly
    key_dt = kg()
    dt = jnp.exp(
        jax.random.uniform(key_dt, (n_heads,))
        * (math.log(s.dt_max) - math.log(s.dt_min))
        + math.log(s.dt_min)
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": normal_init(kg(), (d, proj_out), sc, dtype),
        "conv_w": normal_init(kg(), (s.d_conv, conv_ch), 0.5 / math.sqrt(s.d_conv), dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.log(a).astype(jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm": jnp.ones((d_in,), dtype),
        "out_proj": normal_init(
            kg(), (d_in, d), sc / math.sqrt(2 * cfg.n_layers), dtype
        ),
    }


def spec_mamba2(cfg: ModelConfig, model_axis: str = "model") -> Dict[str, Any]:
    mp = model_axis
    return {
        "in_proj": P(None, mp),
        "conv_w": P(None, mp),
        "conv_b": P(mp),
        "a_log": P(None),
        "dt_bias": P(None),
        "d_skip": P(None),
        "norm": P(mp),
        "out_proj": P(mp, None),
    }


# ---------------------------------------------------------------------------
# SSD chunked algorithm (reference; kernels/ssd_scan mirrors it)
# ---------------------------------------------------------------------------


def segsum(a: jnp.ndarray) -> jnp.ndarray:
    """Segment-sum: out[..., i, j] = sum_{k=j+1..i} a[..., k], -inf for j > i."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    idx = jnp.arange(l)
    return jnp.where(idx[:, None] >= idx[None, :], diff, NEG_INF)


def ssd_reference(
    x: jnp.ndarray,  # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H)  (already softplus'ed, positive)
    a: jnp.ndarray,  # (H,)       (negative; A = -exp(a_log))
    b_mat: jnp.ndarray,  # (B, L, G, N)
    c_mat: jnp.ndarray,  # (B, L, G, N)
    chunk: int,
    h0: jnp.ndarray = None,  # (B, H, P, N) initial state
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD; returns (y (B,L,H,P), final_state (B,H,P,N))."""
    bsz, l_orig, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    if l_orig % chunk:
        # zero-pad to a chunk multiple: dt=0 makes padded steps exact no-ops
        # (decay exp(0)=1, input contribution dt·B·x = 0).
        pad = chunk - l_orig % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    l = x.shape[1]
    nc = l // chunk
    rep = h // g

    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = jnp.repeat(b_mat.reshape(bsz, nc, chunk, g, n), rep, axis=3)
    cc = jnp.repeat(c_mat.reshape(bsz, nc, chunk, g, n), rep, axis=3)

    a_dt = dtc * a[None, None, None, :]  # (B, nc, cl, H), negative
    a_cum = jnp.cumsum(a_dt, axis=2)

    # 1) intra-chunk (diagonal blocks)
    l_mat = jnp.exp(segsum(jnp.moveaxis(a_dt, -1, 2)))  # (B, nc, H, cl, cl)
    y_diag = jnp.einsum(
        "bzlhn,bzshn,bzhls,bzshp->bzlhp", cc, bc, l_mat, xc * dtc[..., None]
    )

    # 2) per-chunk states carried to the boundary (fp32 carry)
    decay_states = jnp.exp(a_cum[:, :, -1:, :] - a_cum)  # (B, nc, cl, H)
    states = jnp.einsum(
        "bzlhn,bzlh,bzlhp->bzhpn",
        bc.astype(jnp.float32),
        (decay_states * dtc).astype(jnp.float32),
        xc.astype(jnp.float32),
    )  # (B, nc, H, P, N) fp32

    # 3) inter-chunk recurrence (sequential scan over chunks)
    chunk_decay = jnp.exp(a_cum[:, :, -1, :]).astype(jnp.float32)  # (B, nc, H)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    else:
        h0 = h0.astype(jnp.float32)

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry  # emit the state *entering* the chunk

    final, prev_states = jax.lax.scan(
        step,
        h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B, nc, H, P, N)

    # 4) contribution of incoming chunk states to outputs
    state_decay = jnp.exp(a_cum)  # (B, nc, cl, H)
    y_off = jnp.einsum(
        "bzlhn,bzhpn,bzlh->bzlhp",
        cc.astype(jnp.float32),
        prev_states,
        state_decay.astype(jnp.float32),
    ).astype(y_diag.dtype)

    y = (y_diag + y_off).reshape(bsz, l, h, p)[:, :l_orig]
    return y, final


def ssd_decode_step(
    state: jnp.ndarray,  # (B, H, P, N)
    x_t: jnp.ndarray,  # (B, H, P)
    dt_t: jnp.ndarray,  # (B, H)
    a: jnp.ndarray,  # (H,)
    b_t: jnp.ndarray,  # (B, G, N)
    c_t: jnp.ndarray,  # (B, G, N)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """O(1) recurrent update:  h <- h·exp(dt·A) + dt·x⊗B ;  y = C·h."""
    bsz, h, p, n = state.shape
    g = b_t.shape[1]
    rep = h // g
    b_h = jnp.repeat(b_t, rep, axis=1)  # (B, H, N)
    c_h = jnp.repeat(c_t, rep, axis=1)
    decay = jnp.exp(dt_t * a[None, :])  # (B, H)
    upd = (dt_t[..., None] * x_t)[..., :, None] * b_h[:, :, None, :]  # (B,H,P,N)
    new_state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, c_h)
    return y, new_state


# ---------------------------------------------------------------------------
# Depthwise causal conv (width d_conv)
# ---------------------------------------------------------------------------


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """x: (B, L, C), w: (W, C) depthwise, left-padded causal."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    l = x.shape[1]
    y = sum(pad[:, i : i + l, :] * w[i][None, None, :] for i in range(width))
    return y + b[None, None, :].astype(y.dtype)


def conv_decode_step(
    window: jnp.ndarray,  # (B, W-1, C) previous inputs
    x_t: jnp.ndarray,  # (B, 1, C)
    w: jnp.ndarray,
    b: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    full = jnp.concatenate([window, x_t], axis=1)  # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", full, w) + b
    return y[:, None, :], full[:, 1:, :]


# ---------------------------------------------------------------------------
# Block forward (train / prefill / decode)
# ---------------------------------------------------------------------------


def _split_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    s, d_in, n_heads, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * gn], axis=-1)
    return z, xbc, dt  # xbc = [x, B, C] conv channels


def _split_xbc(cfg: ModelConfig, xbc: jnp.ndarray):
    s, d_in, n_heads, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    x, b_mat, c_mat = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    bsz, l = x.shape[:2]
    x = x.reshape(bsz, l, n_heads, s.head_dim)
    b_mat = b_mat.reshape(bsz, l, s.n_groups, s.d_state)
    c_mat = c_mat.reshape(bsz, l, s.n_groups, s.d_state)
    return x, b_mat, c_mat


def mamba2_forward(
    params: Dict, cfg: ModelConfig, u: jnp.ndarray, *, use_kernel: bool = False
) -> jnp.ndarray:
    """u: (B, L, d_model) -> (B, L, d_model)."""
    s, d_in, n_heads, _ = _dims(cfg)
    with jax.named_scope(SCOPE_IN_PROJ):
        zxbcdt = u @ params["in_proj"]
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    with jax.named_scope(SCOPE_CONV):
        xbc = jax.nn.silu(causal_conv(xbc, params["conv_w"], params["conv_b"]))
    x, b_mat, c_mat = _split_xbc(cfg, xbc)
    with jax.named_scope(SCOPE_SSD):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        a = -jnp.exp(params["a_log"])
        if use_kernel:
            from repro.kernels.ops import ssd_scan

            y, _ = ssd_scan(x, dt, a, b_mat, c_mat, chunk=s.chunk)
        else:
            y, _ = ssd_reference(x, dt.astype(x.dtype), a, b_mat, c_mat, chunk=s.chunk)
        y = y.astype(u.dtype) + params["d_skip"].astype(u.dtype)[None, None, :, None] * x
    with jax.named_scope(SCOPE_OUT_PROJ):
        y = y.reshape(u.shape[0], u.shape[1], d_in)
        y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
        return y @ params["out_proj"]


def init_mamba2_cache(cfg: ModelConfig, batch: int, dtype) -> Dict:
    s, d_in, n_heads, conv_ch = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, n_heads, s.head_dim, s.d_state), jnp.float32),
    }


def spec_mamba2_cache(cfg: ModelConfig, batch_axes, model_axis="model") -> Dict:
    return {
        "conv": P(batch_axes, None, model_axis),
        "ssm": P(batch_axes, None, None, None),
    }


def mamba2_decode(
    params: Dict, cfg: ModelConfig, u: jnp.ndarray, cache: Dict
) -> Tuple[jnp.ndarray, Dict]:
    """u: (B, 1, d_model); O(1) per token."""
    s, d_in, n_heads, _ = _dims(cfg)
    zxbcdt = u @ params["in_proj"]
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    conv_out, conv_win = conv_decode_step(
        cache["conv"], xbc, params["conv_w"], params["conv_b"]
    )
    xbc = jax.nn.silu(conv_out)
    x, b_mat, c_mat = _split_xbc(cfg, xbc)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B,1,H)
    a = -jnp.exp(params["a_log"])
    y, new_state = ssd_decode_step(
        cache["ssm"],
        x[:, 0].astype(jnp.float32),
        dt[:, 0],
        a,
        b_mat[:, 0].astype(jnp.float32),
        c_mat[:, 0].astype(jnp.float32),
    )
    y = y.astype(u.dtype) + params["d_skip"].astype(u.dtype)[None, :, None] * x[:, 0]
    y = y.reshape(u.shape[0], 1, d_in)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    return y @ params["out_proj"], {"conv": conv_win, "ssm": new_state}
