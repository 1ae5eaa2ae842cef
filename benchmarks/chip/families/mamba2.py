"""Mamba-2 (arXiv:2405.21060, the SSD layer) as a language model that the
program trains: its loss, the benchmark's own weights and each agent's
token stream, made on the device from the seed, and the counts of a
round's work (``chipbench/mamba2_counts.py``).

The model is the program's preset ``repro.configs.mamba2_370m`` with the
configuration's depth and widths, its loss ``repro.models.get_bundle(cfg)
.loss`` on ``{"tokens": ...}``.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import mamba2_counts

ZIPF_ALPHA = 1.1
BIGRAM_SHARE = 0.35


def model_config(cfg: dict, dtype: str):
    """The preset with the configuration's depth and widths."""
    import dataclasses

    from repro.configs import mamba2_370m
    from repro.models import SSMConfig

    if cfg["d_inner"] != cfg["expand"] * cfg["d_model"] or \
            cfg["n_heads"] * cfg["head_dim"] != cfg["d_inner"]:
        raise ValueError("d_inner must be expand * d_model = n_heads * head_dim")
    preset = mamba2_370m.config(dtype)
    ssm = SSMConfig(d_state=cfg["d_state"], d_conv=cfg["d_conv"], expand=cfg["expand"],
                    head_dim=cfg["head_dim"], n_groups=cfg["n_groups"], chunk=cfg["chunk"])
    return dataclasses.replace(
        preset, n_layers=cfg["n_layers"], d_model=cfg["d_model"],
        vocab_size=cfg["vocab_size"], tie_embeddings=cfg["tie_embeddings"],
        norm_eps=cfg["norm_eps"], ssm=ssm)


def program_loss(cfg: dict, dtype: str):
    """The program's loss on a window batch ``(tokens, stream ids)``, its
    matmuls at the configuration's precision (on the TPU the default would
    round float32 operands to bfloat16)."""
    import jax

    from repro.models import get_bundle

    lm_loss = get_bundle(model_config(cfg, dtype)).loss

    def loss(params, batch):
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            return lm_loss(params, {"tokens": batch[0]})

    return loss


def program_shapes(cfg: dict, dtype: str):
    import jax

    from repro.models import get_bundle

    return jax.eval_shape(get_bundle(model_config(cfg, dtype)).init, jax.random.PRNGKey(0))


def init_params(cfg: dict, key, dtype: str = "float32"):
    """Random weights in the program's layout and dtypes (``assumed.init``
    of the configuration): A in ``A_init_range`` and dt through softplus in
    ``[dt_min, dt_max]``, Mamba-2's published ranges, so that a deep stack
    of random blocks stays finite."""
    import jax
    import jax.numpy as jnp

    d, di, h, v = cfg["d_model"], cfg["d_inner"], cfg["n_heads"], cfg["vocab_size"]
    n_l, k_conv = cfg["n_layers"], cfg["d_conv"]
    conv = di + 2 * cfg["n_groups"] * cfg["d_state"]
    width = di + conv + h
    ks = iter(jax.random.split(key, 8))

    def uniform(shape, bound, lo=None):
        lo = -bound if lo is None else lo
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, bound)

    a = uniform((n_l, h), cfg["A_init_range"][1], cfg["A_init_range"][0])
    log_dt = uniform((n_l, h), math.log(cfg["dt_max"]), math.log(cfg["dt_min"]))
    dt = jnp.exp(log_dt)
    mixer = {
        "in_proj": uniform((n_l, d, width), 1.0 / math.sqrt(d)),
        "conv_w": uniform((n_l, k_conv, conv), 1.0 / math.sqrt(k_conv)),
        "conv_b": uniform((n_l, conv), 1.0 / math.sqrt(k_conv)),
        "a_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
        "d_skip": jnp.ones((n_l, h), jnp.float32),
        "norm": jnp.ones((n_l, di), jnp.float32),
        "out_proj": uniform((n_l, di, d), 1.0 / math.sqrt(di)) / math.sqrt(n_l),
    }
    params = {
        "embed": 0.02 * jax.random.normal(next(ks), (v, d), jnp.float32),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "head_layers": [],
        "layers": {"pos0": {"norm1": {"scale": jnp.ones((n_l, d), jnp.float32)},
                            "mixer": mixer}},
    }
    want = program_shapes(cfg, dtype)
    return jax.tree.map(lambda p, w: p.astype(w.dtype), params, want)


def _stream(key, n_tokens: int, vocab: int):
    """One agent's stream (``assumed.data``): Zipf ids with bigrams, then
    relabelled by the agent's own permutation of the vocabulary."""
    import jax
    import jax.numpy as jnp

    k_zipf, k_follow, k_perm = jax.random.split(key, 3)
    probs = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -ZIPF_ALPHA
    cdf = jnp.cumsum(probs / jnp.sum(probs))
    base = jnp.minimum(jnp.searchsorted(cdf, jax.random.uniform(k_zipf, (n_tokens,))), vocab - 1)
    follow = jax.random.uniform(k_follow, (n_tokens,)) < BIGRAM_SHARE
    follow = follow.at[0].set(False)
    tokens = jnp.where(follow, (jnp.roll(base, 1) * 7 + 1) % vocab, base)
    return jax.random.permutation(k_perm, vocab)[tokens].astype(jnp.int32)


def raw_data(cfg: dict, traffic: dict, key):
    """Every agent's stream cut into ``samples_per_agent`` packed windows of
    ``seq`` tokens, on the device: windows ``(agents * m, seq)`` int32,
    agent by agent, and each window's stream id ``(agents * m,)``."""
    import jax
    import jax.numpy as jnp

    a, m, seq = traffic["agents"], traffic["samples_per_agent"], traffic["seq"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, a)
        tokens = jax.vmap(lambda k: _stream(k, m * seq, cfg["vocab_size"]))(keys)
        return tokens.reshape(a * m, seq), jnp.repeat(jnp.arange(a, dtype=jnp.int32), m)

    return make(key)


def dataset(cfg: dict, traffic: dict, seed: int, key):
    """The windows split by the program, labelled by stream: the paper's
    heterogeneous split (sorted by label, cut contiguously) hands each
    agent the windows of its own stream."""
    from repro.data.federated import FederatedDataset

    x, y = raw_data(cfg, traffic, key)
    return FederatedDataset.from_arrays(
        np.asarray(x), np.asarray(y), traffic["agents"], heterogeneous=True,
        test_fraction=0.0, seed=seed)


def flops_per_round(cfg: dict, traffic: dict) -> float:
    return mamba2_counts.train_flops_per_round(cfg, traffic)


def bytes_per_round(cfg: dict, traffic: dict) -> float:
    return mamba2_counts.train_bytes_per_round(cfg, traffic)


def ssd_flops_per_round(cfg: dict, traffic: dict) -> float:
    return mamba2_counts.ssd_flops_per_round(cfg, traffic)


def ssd_bytes_per_round(cfg: dict, traffic: dict) -> float:
    return mamba2_counts.ssd_bytes_per_round(cfg, traffic)
