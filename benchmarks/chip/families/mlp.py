"""The PISCO paper's section 5.2 model, a ``d_in - hidden - classes``
sigmoid MLP with a softmax cross-entropy, and its data: the benchmark's own
weights and the synthetic digit clusters the repository stands in for MNIST
with, both made on the device from the seed."""
from __future__ import annotations

import numpy as np


def program_loss(cfg: dict, dtype: str):
    """The program's loss, its matmuls at the configuration's precision
    (on the TPU the default would round float32 operands to bfloat16)."""
    import jax

    from repro.models.simple import mlp_loss

    def loss(params, batch):
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            return mlp_loss(params, batch)

    return loss


def program_shapes(cfg: dict, dtype: str):
    import jax

    from repro.models.simple import mlp_init

    return jax.eval_shape(
        lambda k: mlp_init(k, cfg["d_in"], cfg["hidden"], cfg["n_classes"]),
        jax.random.PRNGKey(0))


def init_params(cfg: dict, key, dtype: str = "float32"):
    """Normal(0, 0.1) weights and zero biases, in the program's layout."""
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(key)
    h, c, d = cfg["hidden"], cfg["n_classes"], cfg["d_in"]
    dt = jnp.dtype(dtype)
    return {
        "w1": (0.1 * jax.random.normal(k1, (h, d), jnp.float32)).astype(dt),
        "c1": jnp.zeros((h,), dt),
        "w2": (0.1 * jax.random.normal(k2, (c, h), jnp.float32)).astype(dt),
        "c2": jnp.zeros((c,), dt),
    }


def _digits(key, n: int, d: int, classes: int):
    """Synthetic digits: one sparse template per class in [0, 1], plus
    Normal(0, 0.15) pixel noise, clipped to [0, 1]."""
    import jax
    import jax.numpy as jnp

    kt, km, kl, kn = jax.random.split(key, 4)
    templates = jax.random.uniform(kt, (classes, d)) * (jax.random.uniform(km, (classes, d)) < 0.2)
    labels = jax.random.randint(kl, (n,), 0, classes, jnp.int32)
    x = jnp.clip(templates[labels] + 0.15 * jax.random.normal(kn, (n, d)), 0.0, 1.0)
    return x, labels


def raw_data(cfg: dict, traffic: dict, key):
    """``samples_per_agent`` digits for every agent, on the device."""
    import jax

    n = traffic["agents"] * traffic["samples_per_agent"]
    return jax.jit(_digits, static_argnums=(1, 2, 3))(key, n, cfg["d_in"], cfg["n_classes"])


def dataset(cfg: dict, traffic: dict, seed: int, key):
    """The raw digits split the paper's way by the program: sorted by label
    and cut contiguously, so each agent holds one or two classes."""
    from repro.data.federated import FederatedDataset

    x, y = raw_data(cfg, traffic, key)
    return FederatedDataset.from_arrays(
        np.asarray(x), np.asarray(y), traffic["agents"], heterogeneous=True,
        test_fraction=0.0, seed=seed)


def sample_bytes(cfg: dict) -> int:
    return 4 * cfg["d_in"] + 4  # float32 pixels and an int32 label


def flops_per_round(cfg: dict, traffic: dict) -> float:
    from chipbench import counts

    samples = traffic["agents"] * (traffic["t_o"] + 1) * traffic["batch"]
    return samples * counts.mlp_train_flops_per_sample(cfg)


def bytes_per_round(cfg: dict, traffic: dict) -> float:
    from chipbench import counts

    a = traffic["agents"]
    return (counts.pisco_round_state_bytes(a, 4 * counts.mlp_param_count(cfg))
            + counts.round_batch_bytes(a, traffic["t_o"], traffic["batch"], sample_bytes(cfg)))

