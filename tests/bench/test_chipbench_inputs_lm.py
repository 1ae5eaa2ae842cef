"""The LM cell's weights and token windows are made from the seed alone,
each agent holds its own stream, and the reference gathers the sampler's
windows by its own copy of the split and the draw."""
import jax
import numpy as np
import pytest

import _chipbench_lm as lm
from chipbench import registry

BIG = 2**31 + 12_345  # seeds past 32 bits


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return registry.load_cell(lm.make_root(tmp_path_factory.mktemp("bench")), lm.NAME)


def test_weights_are_pure_in_the_seed_and_in_the_published_ranges(cell):
    train = cell.runner.train
    make = lambda s: train.build(cell, s).init(train.key_of(s))
    a, b, c = (jax.tree.leaves(make(s)) for s in (BIG, BIG, BIG + 1))
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != z).any() for x, z in zip(a, c) if x.size > 1)
    mixer = make(BIG)["layers"]["pos0"]["mixer"]
    a_neg = np.exp(np.asarray(mixer["a_log"]))
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))
    assert (a_neg >= 1.0).all() and (a_neg <= 16.0).all()
    assert (dt >= 1e-3 * (1 - 1e-5)).all() and (dt <= 0.1 * (1 + 1e-5)).all()


def test_each_agent_holds_its_own_stream(cell):
    """Windows are pure in the seed; the paper's split by label hands each
    agent the windows of its own stream, whose ids the agent's own
    permutation of the vocabulary relabels."""
    train, t = cell.runner.train, cell.traffic
    data = [train.build(cell, s).sampler.data for s in (BIG, BIG, BIG + 1)]
    assert (data[0].x_train == data[1].x_train).all()
    assert not (data[0].x_train == data[2].x_train).all()
    x, y = data[0].x_train, data[0].y_train
    assert x.shape == (t["agents"], t["samples_per_agent"], t["seq"]) and x.dtype == np.int32
    assert (y == np.arange(t["agents"])[:, None]).all()
    assert x.min() >= 0 and x.max() < cell.config["vocab_size"]
    # the streams' most frequent ids differ
    top = [np.bincount(x[i].ravel(), minlength=cell.config["vocab_size"]).argmax()
           for i in range(t["agents"])]
    assert len(set(top)) == t["agents"]


def test_reference_windows_follow_the_samplers_documented_rule(cell):
    from repro.core.driver import sample_block

    train, t = cell.runner.train, cell.traffic
    prog = train.build(cell, BIG)
    ref = cell.module("reference", "federated")
    x, y = cell.family.raw_data(cell.config, t, train.key_of(BIG + 1))
    rows = ref.split_rows(np.asarray(y), t["agents"], BIG)
    batches = ref.make_batches(x, y, rows, BIG, t["t_o"], t["batch"])
    r = t["block_rounds"]
    local, comm = sample_block(prog.sampler, 0, r)
    for k in range(r):
        want = jax.tree.map(lambda lo, c: np.concatenate([lo[k], c[k][None]]), local, comm)
        assert all((np.asarray(a) == b).all()
                   for a, b in zip(jax.tree.leaves(batches(k)), jax.tree.leaves(want)))
