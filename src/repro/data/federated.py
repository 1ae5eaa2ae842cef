"""Federated data partitioning + per-round minibatch sampling.

The paper's heterogeneity protocol (§5): *sort the dataset by label and split
it contiguously* across agents, so each agent sees a disjoint label slice —
extreme non-IID.  ``partition_iid`` is the shuffled control.

:class:`RoundSampler` produces exactly what one PISCO round consumes
(Algorithm 1 uses T_o + 1 fresh minibatches per agent per round):
``local_batches`` with leaves shaped (T_o, n_agents, b, ...) and a
``comm_batch`` with leaves (n_agents, b, ...).  It draws the minibatch
indices on the host and gathers the rows on the device, from a copy of the
training set that stays there (:attr:`FederatedDataset.resident_train`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span

# Domain-separation tags (repro.core.topology idiom): every RNG stream in the
# data path is keyed by (tag, seed[, round]) so equal seeds can never alias two
# different draws.  _PARTITION_TAG fixes the historical bug where the iid
# partition permutation reused the train/test-split stream verbatim;
# _SAMPLER_TAG keys the per-round minibatch stream, making RoundSampler a pure
# function of (seed, round_idx) instead of a stateful call-order-dependent one.
_PARTITION_TAG = 0x9B1D
_SAMPLER_TAG = 0x5A3D


def _derive_seed(tag: int, seed: int) -> int:
    """Collapse (tag, seed) into one int for APIs taking a scalar seed."""
    return int(np.random.SeedSequence((int(tag), int(seed))).generate_state(1)[0])


def partition_sorted(
    x: np.ndarray, y: np.ndarray, n_agents: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Sort by label, split contiguously: (n_agents, m, ...), (n_agents, m)."""
    order = np.argsort(y, kind="stable")
    xs, ys = x[order], y[order]
    m = len(y) // n_agents
    xs = xs[: m * n_agents].reshape(n_agents, m, *x.shape[1:])
    ys = ys[: m * n_agents].reshape(n_agents, m)
    return xs, ys


def partition_iid(
    x: np.ndarray, y: np.ndarray, n_agents: int, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(y))
    xs, ys = x[order], y[order]
    m = len(y) // n_agents
    xs = xs[: m * n_agents].reshape(n_agents, m, *x.shape[1:])
    ys = ys[: m * n_agents].reshape(n_agents, m)
    return xs, ys


@dataclasses.dataclass
class FederatedDataset:
    """Agent-partitioned dataset with train/test split."""

    x_train: np.ndarray  # (A, m, ...)
    y_train: np.ndarray  # (A, m)
    x_test: np.ndarray  # (N_test, ...)
    y_test: np.ndarray  # (N_test,)

    @property
    def n_agents(self) -> int:
        return self.x_train.shape[0]

    @property
    def samples_per_agent(self) -> int:
        return self.x_train.shape[1]

    @functools.cached_property
    def resident_train(self) -> Tuple[jax.Array, jax.Array]:
        """The training set on the default device, flattened to
        ``(A * m, ...)`` and ``(A * m,)``: put once per dataset, the first
        time a sampler gathers from it, and shared by every sampler over
        it.  ``x_train`` / ``y_train`` stay the host arrays.  Put eagerly even
        when the first gather is traced (``jax.eval_shape`` of a sampler),
        so the cached copy is never a tracer."""
        rows = self.n_agents * self.samples_per_agent
        with jax.ensure_compile_time_eval():
            return (jnp.asarray(self.x_train.reshape(rows, *self.x_train.shape[2:])),
                    jnp.asarray(self.y_train.reshape(rows)))

    @classmethod
    def from_arrays(
        cls,
        x: np.ndarray,
        y: np.ndarray,
        n_agents: int,
        *,
        heterogeneous: bool = True,
        test_fraction: float = 0.2,
        seed: int = 0,
    ) -> "FederatedDataset":
        rng = np.random.default_rng(seed)
        order = rng.permutation(len(y))
        n_test = int(len(y) * test_fraction)
        test_idx, train_idx = order[:n_test], order[n_test:]
        if heterogeneous:
            xs, ys = partition_sorted(x[train_idx], y[train_idx], n_agents)
        else:
            # Domain-separated partition seed: passing ``seed`` verbatim made
            # the iid partition permutation the *same stream* as the
            # train/test split above, correlating which samples land where.
            xs, ys = partition_iid(
                x[train_idx], y[train_idx], n_agents,
                seed=_derive_seed(_PARTITION_TAG, seed),
            )
        return cls(xs, ys, x[test_idx], y[test_idx])


@jax.jit
def _gather_batches(x: jax.Array, y: jax.Array, idx: jax.Array):
    """``(local, comm)`` minibatches at ``idx`` from a flattened training
    set (``FederatedDataset.resident_train``).

    ``idx`` holds per-agent sample indices shaped ``(..., T_o + 1, A, b)``,
    with or without a leading round axis; agent ``i``'s rows start at
    ``i * m``.  The local and comm index slices are gathered apart, so no
    slice or copy of the batches follows.  The indices are in ``[0, m)`` by
    construction, so the gather skips the bounds mask."""
    a = idx.shape[-2]
    rows = idx + (jnp.arange(a, dtype=idx.dtype) * (x.shape[0] // a))[:, None]

    def take(ids):
        return (x.at[ids].get(mode="promise_in_bounds"),
                y.at[ids].get(mode="promise_in_bounds"))

    return take(rows[..., :-1, :, :]), take(rows[..., -1, :, :])


class RoundSampler:
    """Sampler matching the trainer's contract: sampler(k) ->
    (local_batches [T_o, A, b, ...], comm_batch [A, b, ...]).

    Round ``k``'s minibatch indices are a **pure function of
    ``(seed, round_idx)``** — the same domain-separated
    ``np.random.default_rng((tag, seed, k))`` idiom the topology processes
    use — so eval replays, checkpoint resume, out-of-order calls, and every
    driver (loop, scan blocks at any boundary, events) see bit-identical
    batches for the same round.  The historical sampler drew from one
    stateful stream and silently ignored ``round_idx``; pass
    ``legacy_stream=True`` to reproduce that call-order-dependent behavior.
    """

    def __init__(
        self, data: FederatedDataset, batch_size: int, t_o: int, seed: int = 0,
        *, legacy_stream: bool = False,
    ):
        self.data = data
        self.b = batch_size
        self.t_o = t_o
        self.seed = seed
        self.legacy_stream = legacy_stream
        self._rng = np.random.default_rng(seed) if legacy_stream else None

    def _round_idx(self, round_idx: int, n_rounds: int = 1) -> np.ndarray:
        """(n_rounds, T_o + 1, A, b) sample indices for rounds starting at
        ``round_idx``, each round's draw pure in ``(seed, round)``.  Round
        indices are mapped to nonnegative ints (SeedSequence rejects
        negatives); the init probe ``sampler(-1)`` lands on its own round."""
        a, m = self.data.n_agents, self.data.samples_per_agent
        if self.legacy_stream:
            return self._rng.integers(
                0, m, size=(n_rounds, self.t_o + 1, a, self.b)
            )
        return np.stack([
            np.random.default_rng(
                (_SAMPLER_TAG, int(self.seed), int(round_idx + r) % (1 << 63))
            ).integers(0, m, size=(self.t_o + 1, a, self.b))
            for r in range(n_rounds)
        ])

    def _nbytes(self, idx: np.ndarray) -> int:
        """Bytes of the x and y batches gathered at ``idx``."""
        return idx.size * (self.data.x_train[0, 0].nbytes + self.data.y_train.itemsize)

    def _gather(self, idx: np.ndarray, rounds: int):
        """Put ``idx`` on the device as int32 and gather its batches from
        the resident training set: the profiler spans ``repro.sample.put``
        (``bytes`` of the indices) and ``repro.sample.gather`` (the
        gather's dispatch, ``bytes`` of the batches it makes)."""
        x, y = self.data.resident_train
        idx = idx.astype(np.int32)
        with span("sample.put", rounds=rounds, bytes=idx.nbytes):
            idx_dev = jnp.asarray(idx)
        with span("sample.gather", rounds=rounds, bytes=self._nbytes(idx), on="device"):
            return _gather_batches(x, y, idx_dev)

    def __call__(self, round_idx: int):
        return self._gather(self._round_idx(round_idx)[0], 1)

    def sample_block(self, start: int, stop: int):
        """Batches for rounds ``[start, stop)`` with a leading round axis, in
        one index put + one device gather (the scan driver's fast path).

        Each round's indices are drawn from that round's own pure stream, so
        a block draw and ``stop - start`` sequential ``__call__``s see
        identical batches regardless of where block boundaries fall.  The
        index draw stays on the host and is left out of both spans."""
        return self._gather(self._round_idx(start, stop - start), stop - start)
