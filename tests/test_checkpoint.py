"""Checkpoint round-trip regression tests.

The serving path depends on checkpoints being *exact*: a trained federated
final state must restore bitwise-identically (the delta exporter and the
bit-identity pin of the decode engine both assume it), and every leaf dtype
must survive — including ml_dtypes extension dtypes (bfloat16), which npz
silently erases to raw void bytes unless the manifest restores them.
"""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from conftest import make_logreg_problem
from repro.checkpoint import (
    latest_checkpoint,
    read_manifest,
    restore_checkpoint,
    save_checkpoint,
)


def _leaves_bit_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, f"dtype {x.dtype} != {y.dtype}"
        assert x.shape == y.shape
        assert x.tobytes() == y.tobytes(), "bit patterns differ"


def test_trained_state_round_trip_bitwise(tmp_path):
    """Save -> restore of a real trained PISCO final state is bitwise exact
    (namedtuple state comes back as a plain tuple, same leaf order)."""
    from repro.core import (
        PiscoConfig, make_sparse_topology, replicate_params, sparse_mixing,
        run_training,
    )

    n = 4
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, eta_c=1.0, p=0.5, seed=0)
    hist = run_training(
        "pisco", loss_fn, replicate_params({"w": jnp.zeros(d)}, n), cfg,
        sparse_mixing(make_sparse_topology("ring", n)), sampler_factory(2), rounds=3,
    )
    state = hist.final_state
    path = save_checkpoint(str(tmp_path), 3, state)
    step, restored = restore_checkpoint(path)
    assert step == 3
    assert isinstance(restored, tuple)
    assert len(restored) == len(state)
    _leaves_bit_equal(restored, tuple(state))
    # the serving exporter's contract: X is recoverable as field 0
    _leaves_bit_equal(restored[0], state.x)


@pytest.mark.parametrize(
    "dtype",
    [np.float32, np.float16, np.int32, np.int8, ml_dtypes.bfloat16],
    ids=["f32", "f16", "i32", "i8", "bf16"],
)
def test_dtype_preserved_through_round_trip(tmp_path, dtype):
    rng = np.random.default_rng(0)
    tree = {
        "a": rng.normal(size=(5, 3)).astype(dtype),
        "nested": [rng.normal(size=(4,)).astype(dtype)],
    }
    path = save_checkpoint(str(tmp_path), 1, tree)
    _, restored = restore_checkpoint(path)
    _leaves_bit_equal(restored, tree)


def test_mixed_dtype_tree_round_trip(tmp_path):
    tree = {
        "w": np.arange(6, dtype=np.float32).reshape(2, 3),
        "h": np.arange(4, dtype=ml_dtypes.bfloat16),
        "c": np.arange(3, dtype=np.int32),
    }
    path = save_checkpoint(str(tmp_path), 0, tree)
    _, restored = restore_checkpoint(path)
    _leaves_bit_equal(restored, tree)


def test_manifest_metadata_round_trip(tmp_path):
    meta = {"kind": "fleet", "model": {"name": "tiny", "n_layers": 2}}
    path = save_checkpoint(
        str(tmp_path), 5, {"x": np.zeros(3)}, metadata=meta
    )
    m = read_manifest(path)
    assert m["step"] == 5
    assert m["metadata"] == meta
    assert m["keys"] == ["d:x"]
    assert m["dtypes"] == ["float64"]
    # default: no metadata -> empty dict, never a KeyError
    p2 = save_checkpoint(str(tmp_path), 6, {"x": np.zeros(3)})
    assert read_manifest(p2)["metadata"] == {}


def test_latest_checkpoint_picks_max_step(tmp_path):
    assert latest_checkpoint(str(tmp_path)) is None
    for s in (2, 10, 7):
        save_checkpoint(str(tmp_path), s, {"x": np.zeros(1)})
    assert latest_checkpoint(str(tmp_path)).endswith("ckpt_10.npz")
