"""The fleet's minibatches for the reference, from the raw arrays alone.

Imports nothing of the program.  The rules are those that the program's
``data/federated.py`` documents, written out again here so that a wrong
split, agent or sample on the program's side shows as a gap:

- the split (``FederatedDataset.from_arrays`` with ``test_fraction=0`` and
  the paper's heterogeneous protocol): the samples in the order of
  ``np.random.default_rng(seed).permutation(n)``, then sorted by label
  (stable) and cut contiguously, ``m = n // agents`` to an agent;
- the draw (``RoundSampler``): round ``k``'s positions in each agent's
  share are ``np.random.default_rng((0x5A3D, seed, k mod 2**63))
  .integers(0, m, size=(T_o + 1, agents, batch))``; the first ``T_o`` are
  the local steps', the last the communication step's.  The initial
  gradient is taken on the last minibatch of round -1.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

SAMPLER_TAG = 0x5A3D


def split_rows(labels: np.ndarray, n_agents: int, seed: int) -> np.ndarray:
    """(agents, m) indices into the raw arrays: agent ``i``'s ``j``-th sample."""
    order = np.random.default_rng(seed).permutation(len(labels))
    order = order[np.argsort(labels[order], kind="stable")]
    m = len(labels) // n_agents
    return order[: m * n_agents].reshape(n_agents, m)


def round_positions(seed: int, k: int, t_o: int, n_agents: int, m: int, batch: int):
    """(T_o + 1, agents, batch) positions in each agent's share, round ``k``."""
    rng = np.random.default_rng((SAMPLER_TAG, int(seed), int(k) % (1 << 63)))
    return rng.integers(0, m, size=(t_o + 1, n_agents, batch))


def make_batches(x, y, rows: np.ndarray, seed: int, t_o: int, batch: int):
    """``batches(k)``: round ``k``'s (inputs, labels), leaves
    (T_o + 1, agents, batch, ...), gathered on the device from the raw
    arrays ``x`` and ``y``."""
    n_agents, m = rows.shape

    def batches(k: int):
        pos = round_positions(seed, k, t_o, n_agents, m, batch)
        idx = jnp.asarray(np.take_along_axis(rows[None], pos, axis=2))
        return x[idx], y[idx]

    return batches
