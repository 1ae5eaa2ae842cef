"""Plain float32 reference of the PISCO paper's section 5.2 MLP loss:
sigmoid hidden layer, linear output, mean softmax cross-entropy.  Imports
nothing of the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# the model is small: every agent's gradient in one vmapped pass
AGENT_CHUNK = 1 << 30


def loss(params, batch, cfg):
    x, y = batch
    h = jax.nn.sigmoid(jnp.dot(x, params["w1"].T, precision=HI) + params["c1"])
    logits = jnp.dot(h, params["w2"].T, precision=HI) + params["c2"]
    gold = jnp.take_along_axis(logits, y[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
