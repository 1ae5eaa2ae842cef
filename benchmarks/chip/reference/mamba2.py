"""Plain float32 reference of a Mamba-2 language model's loss
(arXiv:2405.21060, "Transformers are SSMs").  Imports nothing of the
program; reads the program's parameter layout (``layers.pos0`` stacks the
blocks on a leading axis).

Each block, on a residual stream u of width d_model:

    h = RMSNorm(u)
    z, xBC, dt = h W_in                       (widths d_inner, d_inner + 2GN, H)
    x, B, C = SiLU(causal depthwise conv_4(xBC) + b)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y = (L o C B^T) (dt X) + D x              the SSD in its quadratic (dual) form,
        L[i, j] = exp(sum_{k=j+1..i} dt_k A) for j <= i, else 0
    u <- u + (RMSNorm(y * SiLU(z)) * w_norm) W_out

then the final RMSNorm, the tied head ``E^T`` and the mean cross-entropy
of ``tokens[:, 1:]``.  The quadratic form is the SSD's definition, not the
chunked algorithm the program runs; L is built from segment sums taken
from zero (a masked cumulative sum), so no difference of two long prefix
sums loses digits.

Departures from the published block, all shared with the program: the
gated norm is over the whole of d_inner (one group, as mamba2-370m has);
no dt clamp (the published default limit is (0, inf)).  The published
model runs the residual stream in float32 too.

Computed so that the check fits on one chip beside the state: one agent at
a time (``AGENT_CHUNK``), each block under ``jax.checkpoint``.  The decay
mask of one layer is ``(batch, H, L, L)`` float32: 256 MiB at batch 2,
32 heads and 1024 tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
AGENT_CHUNK = 1


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """(batch, L, C) through a depthwise causal conv of width ``w.shape[0]``."""
    k, n = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(xp[:, i:i + n] * w[i] for i in range(k))


def segment_sums(a):
    """``a`` (..., L) -> (..., L, L): entry [i, j] the sum of ``a[j+1..i]``
    for j <= i, -inf above the diagonal."""
    n = a.shape[-1]
    strict = jnp.tril(jnp.ones((n, n), bool), -1)
    rows = jnp.where(strict, a[..., :, None], 0.0)  # rows[i, j] = a[i] for j < i
    sums = jnp.cumsum(rows, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)), sums, -jnp.inf)


def ssd(x, dt, a, b, c, d_skip):
    """x (batch, L, H, P), dt (batch, L, H), a (H,), b and c (batch, L, G, N):
    y = (L o C B^T)(dt X) + D x."""
    h, g = x.shape[2], b.shape[2]
    decay = jnp.exp(segment_sums(jnp.moveaxis(dt * a, 1, 2)))  # (batch, H, L, L)
    cb = jnp.einsum("bign,bjgn->bgij", c, b, precision=HI)
    cb = jnp.repeat(cb, h // g, axis=1)  # heads of a group share B and C
    scores = cb * decay * jnp.moveaxis(dt, 1, 2)[:, :, None, :]
    y = jnp.einsum("bhij,bjhp->bihp", scores, x, precision=HI)
    return y + d_skip[:, None] * x


def block(u, p, cfg):
    """One Mamba-2 block with its residual."""
    m = p["mixer"]
    di, h, n, g = cfg["d_inner"], cfg["n_heads"], cfg["d_state"], cfg["n_groups"]
    bsz, seq, _ = u.shape
    hn = rms_norm(u, p["norm1"]["scale"], cfg["norm_eps"])
    proj = jnp.einsum("bld,de->ble", hn, m["in_proj"], precision=HI)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * g * n], proj[..., 2 * di + 2 * g * n:]
    xbc = jax.nn.silu(causal_conv(xbc, m["conv_w"], m["conv_b"]))
    x = xbc[..., :di].reshape(bsz, seq, h, di // h)
    b = xbc[..., di:di + g * n].reshape(bsz, seq, g, n)
    c = xbc[..., di + g * n:].reshape(bsz, seq, g, n)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = ssd(x, dt, -jnp.exp(m["a_log"]), b, c, m["d_skip"]).reshape(bsz, seq, di)
    y = rms_norm(y * jax.nn.silu(z), m["norm"], cfg["norm_eps"])
    return u + jnp.einsum("ble,ed->bld", y, m["out_proj"], precision=HI)


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of one agent's windows; ``batch`` is
    ``(tokens (batch, L) int32, stream ids)``."""
    tokens = batch[0]
    u = params["embed"][tokens]
    u, _ = jax.lax.scan(jax.checkpoint(lambda u, p: (block(u, p, cfg), None)),
                        u, params["layers"]["pos0"])
    u = rms_norm(u[:, :-1], params["final_norm"]["scale"], cfg["norm_eps"])
    logits = jnp.einsum("bld,vd->blv", u, params["embed"], precision=HI)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
