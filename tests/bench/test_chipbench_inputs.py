"""Weights and data are made from the seed alone: the same seed gives the
same inputs, any other seed other inputs, seeds past 32 bits included."""
import numpy as np
import pytest

import _chipbench_tiny as tiny
from chipbench import registry

BIG = 2**31 + 12_345  # the driver's seeds are large


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _weights(cell, seed):
    import jax

    run = cell.runner
    return jax.tree.leaves(run.build(cell, seed).init(run.key_of(seed)))


def test_keys_separate_seeds_past_32_bits():
    import jax

    key_of = registry.load_module([tiny.BENCH], "runners", "train").key_of
    keys = [np.asarray(jax.random.key_data(key_of(s))) for s in (1, 1 + 2**32, 2**40, BIG)]
    assert len({k.tobytes() for k in keys}) == 4
    assert (np.asarray(key_of(BIG)) == keys[3]).all()


@pytest.mark.parametrize("name", ["tiny.fleet"])
def test_weights_are_pure_in_the_seed(root, name):
    cell = registry.load_cell(root, name)
    a, b, c = _weights(cell, BIG), _weights(cell, BIG), _weights(cell, BIG + 1)
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != z).any() for x, z in zip(a, c) if x.size > 1)


def test_fleet_data_and_first_batches_are_pure_in_the_seed(root):
    from repro.core.driver import sample_block

    cell = registry.load_cell(root, "tiny.fleet")
    runs = [cell.runner.build(cell, s) for s in (BIG, BIG, BIG + 1)]
    data = [r.sampler.data for r in runs]
    assert (data[0].x_train == data[1].x_train).all()
    assert not (data[0].x_train == data[2].x_train).all()
    # the paper's split: sorted by label, so each agent holds few classes
    assert all(len(np.unique(y)) <= 2 for y in data[0].y_train)
    blocks = [sample_block(r.sampler, 0, 4) for r in runs[:2]]
    assert all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(*(__import__("jax").tree.leaves(b) for b in blocks)))


def test_reference_batches_follow_the_samplers_documented_rule(root):
    """The reference gathers its minibatches from the raw arrays by its own
    copy of the split and the draw; on a sound program they are the
    sampler's, round -1 (the initial gradient's) included."""
    import jax

    from repro.core.driver import sample_block

    cell = registry.load_cell(root, "tiny.fleet")
    run, t = cell.runner, cell.traffic
    prog = run.build(cell, BIG)
    data = cell.module("reference", "federated")
    x, y = cell.family.raw_data(cell.config, t, run.key_of(BIG + 1))
    rows = data.split_rows(np.asarray(y), t["agents"], BIG)
    batches = data.make_batches(x, y, rows, BIG, t["t_o"], t["batch"])
    local, comm = sample_block(prog.sampler, 0, 4)
    for k in range(4):
        want = jax.tree.map(lambda lo, c: np.concatenate([lo[k], c[k][None]]), local, comm)
        assert all((np.asarray(a) == b).all()
                   for a, b in zip(jax.tree.leaves(batches(k)), jax.tree.leaves(want)))
    _, comm0 = prog.sampler(-1)
    assert all((np.asarray(a[-1]) == np.asarray(b)).all()
               for a, b in zip(jax.tree.leaves(batches(-1)), jax.tree.leaves(comm0)))
