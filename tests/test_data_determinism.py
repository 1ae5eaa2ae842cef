"""Data-path determinism: the two bugfixes this subsystem rode in on.

* :class:`RoundSampler` is a pure function of ``(seed, round_idx)`` — same
  round, same batches, regardless of call order, block boundaries, resume
  point, or which driver (loop, scan, events) is asking.  The historical
  sampler drew from one stateful stream and silently ignored ``round_idx``
  (``legacy_stream=True`` reproduces it, pinned here for the record).
* ``FederatedDataset.from_arrays`` derives the iid-partition seed through a
  domain-separation tag: passing ``seed`` verbatim made the partition
  permutation the *same stream* as the train/test split, correlating which
  samples land on which agent with which samples went to test.

The sampler gathers on the device from a resident copy of the training set;
its batches are pinned bit for bit against a plain numpy gather here.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExperimentSpec, Experiment
from repro.data import FederatedDataset, RoundSampler
from repro.data import federated
from repro.data.federated import (
    _PARTITION_TAG, _SAMPLER_TAG, _derive_seed, partition_iid,
)


def _data(n_agents=4, n=80, d=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = np.sign(rng.normal(size=n))
    return FederatedDataset.from_arrays(x, y, n_agents, heterogeneous=True,
                                        seed=seed)


def _flat(batch):
    local, comm = batch
    return [np.asarray(a) for a in (*local, *comm)]


def _assert_batches_equal(a, b):
    for u, v in zip(_flat(a), _flat(b)):
        np.testing.assert_array_equal(u, v)


def _assert_batches_differ(a, b):
    assert any(
        not np.array_equal(u, v) for u, v in zip(_flat(a), _flat(b))
    )


# ---------------------------------------------------------------------------
# RoundSampler purity
# ---------------------------------------------------------------------------


def test_sampler_pure_in_seed_and_round():
    data = _data()
    s = RoundSampler(data, batch_size=4, t_o=2, seed=7)
    _assert_batches_equal(s(5), s(5))  # replay
    # call order cannot matter: interleave arbitrary rounds, then compare
    # round 3 against a fresh sampler that never saw the others
    s(9); s(0); s(42)
    fresh = RoundSampler(data, batch_size=4, t_o=2, seed=7)
    _assert_batches_equal(s(3), fresh(3))
    # different seed or different round: different draws
    _assert_batches_differ(s(3), s(4))
    _assert_batches_differ(s(3), RoundSampler(data, batch_size=4, t_o=2,
                                              seed=8)(3))


def test_sampler_init_probe_has_its_own_round():
    data = _data()
    s = RoundSampler(data, batch_size=4, t_o=2, seed=7)
    _assert_batches_equal(s(-1), s(-1))
    _assert_batches_differ(s(-1), s(0))


def test_sampler_resume_tail_matches_full_block():
    # checkpoint-resume shape: a run repriced/resumed from round 4 must see
    # the same tail stream as the uninterrupted run
    data = _data()
    s = RoundSampler(data, batch_size=4, t_o=2, seed=7)
    full_local, full_comm = s.sample_block(0, 10)
    head = s.sample_block(0, 4)
    tail = s.sample_block(4, 10)
    for arr, h, t in zip(
        (*full_local, *full_comm), (*head[0], *head[1]), (*tail[0], *tail[1])
    ):
        np.testing.assert_array_equal(
            np.asarray(arr), np.concatenate([np.asarray(h), np.asarray(t)])
        )
    # ... and the block draw equals sequential calls across the boundary
    for k in (3, 4, 5):
        _assert_batches_equal(
            s(k),
            (tuple(a[k] for a in full_local), tuple(a[k] for a in full_comm)),
        )


def test_legacy_stream_reproduces_stateful_sampler():
    # the historical behavior, kept behind a flag: one shared stream, the
    # round index ignored — so the same round drawn twice differs, and the
    # indices are exactly the raw default_rng(seed) integer stream
    data = _data()
    s = RoundSampler(data, batch_size=4, t_o=2, seed=7, legacy_stream=True)
    first, second = s(0), s(0)
    _assert_batches_differ(first, second)
    ref = np.random.default_rng(7)
    a, m = data.n_agents, data.samples_per_agent
    idx = ref.integers(0, m, size=(1, 3, a, 4))[0]
    expect = np.take_along_axis(data.y_train[None], idx, axis=2)
    np.testing.assert_array_equal(np.asarray(first[0][1]), expect[:2])


# ---------------------------------------------------------------------------
# The device gather against a numpy gather
# ---------------------------------------------------------------------------


def _typed_data(feat, n_agents=4, m=6, seed=0):
    """A float32 / int32 training set already split per agent."""
    rng = np.random.default_rng(seed)
    x = rng.random((n_agents, m, *feat), np.float32)
    y = rng.integers(0, 10, (n_agents, m)).astype(np.int32)
    return FederatedDataset(x, y, x[0, :0], y[0, :0])


def _drawn(seed, start, n, shape, m, legacy):
    """(n, T_o + 1, A, b) indices of rounds ``start .. start + n - 1`` as the
    sampler documents them: one pure stream per round, or (legacy) one
    stream for the sampler, read from its first call."""
    if legacy:
        return np.random.default_rng(seed).integers(0, m, size=(n, *shape))
    return np.stack([
        np.random.default_rng((_SAMPLER_TAG, seed, k % (1 << 63))).integers(0, m, size=shape)
        for k in range(start, start + n)
    ])


def _numpy_batches(data, idx):
    """``(local, comm)`` at ``idx`` (..., T_o + 1, A, b), gathered by numpy
    from each agent's own rows."""
    agents = np.arange(data.n_agents)[:, None]
    ax = idx.ndim - 3
    t_o = idx.shape[ax] - 1
    xb, yb = data.x_train[agents, idx], data.y_train[agents, idx]
    local = tuple(np.take(a, np.arange(t_o), axis=ax) for a in (xb, yb))
    comm = tuple(np.take(a, -1, axis=ax) for a in (xb, yb))
    return local, comm


@pytest.mark.parametrize("feat", [(3,), (4, 3)], ids=["one_axis", "two_axes"])
@pytest.mark.parametrize("legacy", [False, True], ids=["pure", "legacy"])
@pytest.mark.parametrize("call", ["block", "round", "probe"])
def test_device_gather_matches_a_numpy_gather(call, legacy, feat):
    data = _typed_data(feat)
    t_o, b, seed = 2, 5, 11
    s = RoundSampler(data, batch_size=b, t_o=t_o, seed=seed, legacy_stream=legacy)
    start, n = {"block": (3, 4), "round": (3, 1), "probe": (-1, 1)}[call]
    got = s.sample_block(start, start + n) if call == "block" else s(start)
    idx = _drawn(seed, start, n, (t_o + 1, data.n_agents, b),
                 data.samples_per_agent, legacy)
    want = _numpy_batches(data, idx if call == "block" else idx[0])
    assert all(isinstance(a, jax.Array) for a in (*got[0], *got[1]))
    for g, w in zip(_flat(got), _flat(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(g, w)


def test_token_windows_of_two_streams_stay_with_their_agents():
    """A language model's data: int32 windows of two token streams,
    labelled by stream and split the paper's way, so each of two agents
    holds its own stream.  The device gather draws only that agent's
    windows, bit-identical to a host gather at the same indices."""
    seq, m, t_o, b, seed = 16, 6, 2, 3, 2**31 + 7
    streams = [1000 * i + np.arange(m * seq, dtype=np.int32) for i in range(2)]
    windows = np.concatenate([s.reshape(m, seq) for s in streams])
    data = FederatedDataset.from_arrays(
        windows, np.repeat(np.arange(2, dtype=np.int32), m), 2,
        heterogeneous=True, test_fraction=0.0, seed=seed)
    assert data.x_train.shape == (2, m, seq) and data.x_train.dtype == np.int32
    s = RoundSampler(data, batch_size=b, t_o=t_o, seed=seed)
    local, comm = s.sample_block(0, 3)
    for tokens, stream in (local, comm):
        assert tokens.dtype == jnp.int32
        agent = np.arange(2)[:, None]
        assert (np.asarray(stream) == agent).all()
        assert (np.asarray(tokens) // 1000 == agent[..., None]).all()
    idx = _drawn(seed, 0, 3, (t_o + 1, 2, b), m, False)
    for g, w in zip(_flat((local, comm)), _flat(_numpy_batches(data, idx))):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(g, w)


def test_samplers_over_one_dataset_share_one_resident_copy():
    data = _typed_data((4, 3), n_agents=5, m=7)
    s1 = RoundSampler(data, batch_size=2, t_o=2, seed=1)
    s2 = RoundSampler(data, batch_size=3, t_o=1, seed=2)
    # a first gather under a trace still caches a concrete copy
    jax.eval_shape(lambda: s1.sample_block(0, 2))
    x, y = data.resident_train
    assert not isinstance(x, jax.core.Tracer)
    s1.sample_block(0, 2)
    assert (x.shape, y.shape) == ((35, 4, 3), (35,))
    s2(0)
    s2.sample_block(0, 3)
    assert data.resident_train[0] is x and data.resident_train[1] is y
    assert sum(a.shape == (35, 4, 3) for a in jax.live_arrays()) == 1
    # the host arrays stay numpy; a replaced dataset puts its own copy
    assert isinstance(data.x_train, np.ndarray)
    assert dataclasses.replace(data).resident_train[0] is not x


def test_index_put_carries_four_bytes_per_index(monkeypatch):
    seen = []
    real = federated.span

    def spy(name, **counts):
        seen.append((name, counts))
        return real(name, **counts)

    monkeypatch.setattr(federated, "span", spy)
    data = _typed_data((3,))
    s = RoundSampler(data, batch_size=5, t_o=2, seed=3)
    s.sample_block(0, 4)
    s(7)
    n_idx = 3 * data.n_agents * 5  # (t_o + 1) x agents x batch, per round
    per_index = 4 * 3 + 4  # float32 x of 3 features, int32 y
    assert seen == [
        ("sample.put", {"rounds": 4, "bytes": 4 * 4 * n_idx}),
        ("sample.gather", {"rounds": 4, "bytes": 4 * n_idx * per_index, "on": "device"}),
        ("sample.put", {"rounds": 1, "bytes": 4 * n_idx}),
        ("sample.gather", {"rounds": 1, "bytes": n_idx * per_index, "on": "device"}),
    ]


# ---------------------------------------------------------------------------
# Partition/split domain separation (the from_arrays regression)
# ---------------------------------------------------------------------------


def test_iid_partition_seed_is_domain_separated_from_split():
    seed, n_agents = 7, 4
    rng = np.random.default_rng(3)
    x = rng.normal(size=(100, 2))
    y = np.sign(rng.normal(size=100))
    data = FederatedDataset.from_arrays(x, y, n_agents, heterogeneous=False,
                                        seed=seed)
    # reconstruct the split exactly as from_arrays does
    order = np.random.default_rng(seed).permutation(len(y))
    test_idx, train_idx = order[:20], order[20:]
    np.testing.assert_array_equal(data.x_test, x[test_idx])
    # the partition must come from the tag-derived stream ...
    xs, ys = partition_iid(
        x[train_idx], y[train_idx], n_agents,
        seed=_derive_seed(_PARTITION_TAG, seed),
    )
    np.testing.assert_array_equal(data.x_train, xs)
    np.testing.assert_array_equal(data.y_train, ys)
    # ... NOT from the raw seed, which would alias the split stream above
    xs_old, _ = partition_iid(x[train_idx], y[train_idx], n_agents, seed=seed)
    assert not np.array_equal(data.x_train, xs_old)


def test_derive_seed_separates_tags_and_seeds():
    assert _derive_seed(_PARTITION_TAG, 7) != 7
    assert _derive_seed(_PARTITION_TAG, 7) == _derive_seed(_PARTITION_TAG, 7)
    assert _derive_seed(_PARTITION_TAG, 7) != _derive_seed(_PARTITION_TAG, 8)
    assert _derive_seed(0x1234, 7) != _derive_seed(_PARTITION_TAG, 7)


# ---------------------------------------------------------------------------
# Driver-level pins: every driver sees the same batch stream
# ---------------------------------------------------------------------------


def _run(driver, rounds=8, **spec_kw):
    from repro.models import simple as S

    data = _data(seed=1)
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=data.n_agents, t_o=2, eta_l=0.1, p=0.5,
        seed=0, rounds=rounds, driver=driver, **spec_kw
    )
    exp = Experiment(
        spec,
        loss_fn=S.logreg_loss,
        params0={"w": jnp.zeros((3,), jnp.float32)},
        sampler_factory=lambda s: RoundSampler(
            data, batch_size=4, t_o=s.config.t_o, seed=s.config.seed
        ),
    )
    return exp.run()


def test_rerun_is_bit_identical():
    a, b = _run("scan"), _run("scan")
    assert a.loss == b.loss  # exact float equality, not allclose


def test_scan_block_boundaries_do_not_change_the_stream():
    a = _run("scan", block_size=8)
    b = _run("scan", block_size=3)  # blocks [0,3) [3,6) [6,8)
    np.testing.assert_array_equal(a.loss, b.loss)


def test_all_drivers_see_the_same_batches():
    from repro.sim import FREE_NETWORK

    h_loop = _run("loop")
    h_scan = _run("scan")
    h_ev = _run("events", systems=FREE_NETWORK)
    # scan and the trivial events path execute the same jitted program
    np.testing.assert_array_equal(h_scan.loss, h_ev.loss)
    # the loop driver jits per-round instead of per-block: same stream, same
    # math, tolerance only for fusion-order float differences
    np.testing.assert_allclose(h_loop.loss, h_scan.loss, rtol=1e-5, atol=1e-6)
