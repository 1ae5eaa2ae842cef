"""Substrate tests: data pipeline, optimizers, schedules, checkpointing,
pytree utilities, spec sanitizer, HLO parser."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_sorted_partition_is_heterogeneous():
    from repro.data import partition_sorted, synthetic_mnist

    x, y = synthetic_mnist(2000)
    xs, ys = partition_sorted(x, y, 10)
    # each agent sees at most 2 distinct labels (sorted contiguous split)
    for a in range(10):
        assert len(np.unique(ys[a])) <= 2
    # together they cover all classes
    assert len(np.unique(ys)) == 10


def test_iid_partition_is_balanced():
    from repro.data import partition_iid, synthetic_mnist

    x, y = synthetic_mnist(5000)
    xs, ys = partition_iid(x, y, 10, seed=1)
    for a in range(10):
        assert len(np.unique(ys[a])) == 10


def test_round_sampler_shapes():
    from repro.data import FederatedDataset, RoundSampler, synthetic_a9a

    x, y = synthetic_a9a(2000)
    data = FederatedDataset.from_arrays(x, y, 8, heterogeneous=True)
    samp = RoundSampler(data, batch_size=16, t_o=3)
    (lx, ly), (cx, cy) = samp(0)
    assert lx.shape == (3, 8, 16, 124) and ly.shape == (3, 8, 16)
    assert cx.shape == (8, 16, 124) and cy.shape == (8, 16)


def test_synthetic_data_deterministic():
    from repro.data import synthetic_a9a, synthetic_lm_tokens

    x1, y1 = synthetic_a9a(100, seed=5)
    x2, y2 = synthetic_a9a(100, seed=5)
    np.testing.assert_array_equal(x1, x2)
    t1 = synthetic_lm_tokens(1000, 64, seed=2)
    t2 = synthetic_lm_tokens(1000, 64, seed=2)
    np.testing.assert_array_equal(t1, t2)
    assert t1.max() < 64 and t1.min() >= 0


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", ["sgd", "momentum", "adam", "adamw"])
def test_optimizers_descend_quadratic(make):
    import repro.optim as O

    opt = {
        "sgd": lambda: O.sgd(0.1),
        "momentum": lambda: O.momentum(0.05),
        "adam": lambda: O.adam(0.1),
        "adamw": lambda: O.adamw(0.1, weight_decay=0.0),
    }[make]()
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        updates, state = opt.update(g, state, params)
        params = O.apply_updates(params, updates)
    assert float(loss(params)) < 1e-2


def test_schedules_endpoints():
    import repro.optim as O

    c = O.constant(0.1)
    assert float(c(jnp.asarray(100))) == pytest.approx(0.1)
    cd = O.cosine_decay(1.0, 100, final=0.1)
    assert float(cd(jnp.asarray(0))) == pytest.approx(1.0)
    assert float(cd(jnp.asarray(100))) == pytest.approx(0.1)
    wc = O.warmup_cosine(1.0, 10, 110)
    assert float(wc(jnp.asarray(0))) == pytest.approx(0.0)
    assert float(wc(jnp.asarray(10))) == pytest.approx(1.0, abs=1e-3)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint

    tree = {
        "params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "opt": [np.zeros(2), np.ones(3)],
        "meta": (np.asarray(7),),
    }
    p1 = save_checkpoint(str(tmp_path), 10, tree)
    save_checkpoint(str(tmp_path), 20, tree)
    assert latest_checkpoint(str(tmp_path)).endswith("ckpt_20.npz")
    step, restored = restore_checkpoint(p1)
    assert step == 10
    np.testing.assert_array_equal(restored["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(restored["opt"][1], tree["opt"][1])
    assert restored["meta"][0] == 7


def test_checkpoint_pisco_state(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.core.pisco import PiscoState

    state = PiscoState(
        x={"w": jnp.ones((4, 3))}, y={"w": jnp.zeros((4, 3))},
        g={"w": jnp.full((4, 3), 2.0)}, step=jnp.asarray(5, jnp.int32),
    )
    p = save_checkpoint(str(tmp_path), 5, state)
    step, tree = restore_checkpoint(p)
    x, y, g, stp, ef, opt = tree
    np.testing.assert_array_equal(x["w"], np.ones((4, 3)))
    assert int(stp) == 5
    assert ef == ()  # compression off => empty error-feedback slot
    assert opt == ()  # no update rules bound => empty optimizer slot


# ---------------------------------------------------------------------------
# pytree utils
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_tree_agent_mix_matches_matmul(seed):
    """Both edge-list gossip primitives (gather + segment_sum, and the
    weighted shifts the ring takes) against W @ x."""
    from repro.core.mixing import sparse_mixing
    from repro.core.topology import make_sparse_topology
    from repro.utils.pytree import tree_agent_mean, tree_agent_mix_sparse

    rng = np.random.default_rng(seed)
    n = 6
    topo = make_sparse_topology("ring", n)
    w = topo.dense_w()
    tree = {"a": jnp.asarray(rng.normal(size=(n, 4))), "b": jnp.asarray(rng.normal(size=(n, 2, 3)))}
    e = topo.edges
    senders = jnp.asarray(np.concatenate([e[:, 0], e[:, 1]]))
    receivers = jnp.asarray(np.concatenate([e[:, 1], e[:, 0]]))
    edge_w = jnp.asarray(np.concatenate([topo.edge_weight] * 2), jnp.float32)
    self_w = jnp.asarray(topo.self_weight, jnp.float32)
    for mixed in (
        tree_agent_mix_sparse(tree, senders, receivers, edge_w, self_w, n),
        sparse_mixing(topo).gossip(tree),
    ):
        for k in tree:
            ref = np.tensordot(w, np.asarray(tree[k]), axes=1)
            np.testing.assert_allclose(np.asarray(mixed[k]), ref, atol=1e-5)
    avg = tree_agent_mean(tree)
    np.testing.assert_allclose(
        np.asarray(avg["a"]), np.tile(np.asarray(tree["a"]).mean(0, keepdims=True), (n, 1)),
        atol=1e-6,
    )


def test_tree_helpers():
    from repro.utils.pytree import tree_bytes, tree_size, tree_sq_norm, tree_stack, tree_unstack

    trees = [{"w": jnp.ones(3) * i} for i in range(4)]
    stacked = tree_stack(trees)
    assert stacked["w"].shape == (4, 3)
    back = tree_unstack(stacked, 4)
    assert float(back[2]["w"][0]) == 2.0
    assert tree_size(stacked) == 12
    assert tree_bytes(stacked) == 48
    assert float(tree_sq_norm({"w": jnp.array([3.0, 4.0])})) == pytest.approx(25.0)


# ---------------------------------------------------------------------------
# launch specs + HLO parsing
# ---------------------------------------------------------------------------


def test_sanitize_specs_drops_indivisible():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_debug_mesh
    from repro.launch.specs import sanitize_specs, stack_spec_tree

    mesh = make_debug_mesh((1, 1), ("data", "model"))
    # model axis size 1 always divides; fake a bigger mesh via shape math:
    import jax

    specs = {"w": P(None, "model"), "v": P("model")}
    shapes = {
        "w": jax.ShapeDtypeStruct((4, 6), jnp.float32),
        "v": jax.ShapeDtypeStruct((5,), jnp.float32),
    }
    fixed, report = sanitize_specs(specs, shapes, mesh)
    assert fixed["w"] == P(None, "model")  # 6 % 1 == 0
    stacked = stack_spec_tree(specs, ("data",))
    assert stacked["w"] == P("data", None, "model")


def test_hlo_shape_bytes_and_collectives():
    from repro.utils.hlo import collective_bytes, shape_bytes

    assert shape_bytes("f32[2,3]") == 24
    assert shape_bytes("bf16[4,4]{1,0}") == 32
    assert shape_bytes("(f32[2], s32[3])") == 8 + 12
    hlo = """
  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[32,64]{1,0} all-gather(%y), dimensions={0}
  %cp = f32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %ars = f32[16]{0} all-reduce-start(%w)
  %ard = f32[16]{0} all-reduce-done(%ars)
  %unrelated = f32[2]{0} add(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 16 * 128 * 4 + 16 * 4
    assert out["all-gather"] == 32 * 64 * 2
    assert out["collective-permute"] == 32
    assert out["n_all-reduce"] == 2
    assert out["total"] > 0


def test_roofline_terms():
    from repro.utils.hlo import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, Roofline

    r = Roofline.from_counts(
        1e12, 1e9, 1e8, model_flops=2e14, n_chips=256
    )
    assert r.compute_s == pytest.approx(1e12 / PEAK_FLOPS_BF16)
    assert r.memory_s == pytest.approx(1e9 / HBM_BW)
    assert r.collective_s == pytest.approx(1e8 / ICI_BW)
    assert r.dominant == "compute"
    assert r.useful_ratio == pytest.approx(2e14 / (1e12 * 256))


def test_add_fsdp_axis_greedy():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_debug_mesh
    from repro.launch.specs import add_fsdp_axis

    mesh = make_debug_mesh((1, 1), ("data", "model"))
    specs = {"w": P(None, None, "model"), "n": P(None)}
    shapes = {
        "w": jax.ShapeDtypeStruct((2, 4096, 128), jnp.float32),
        "n": jax.ShapeDtypeStruct((2, 64), jnp.float32),
    }
    out = add_fsdp_axis(specs, shapes, mesh, "data", skip_leading=1)
    assert out["w"] == P(None, "data", "model")  # first big unsharded dim
    assert out["n"] == P()  # below min_dim: untouched


def test_wire_corrected_collective_bytes():
    from repro.utils.hlo import collective_bytes

    hlo = """
  %p = bf16[64]{0} parameter(0)
  %wrapped_convert = f32[64]{0} fusion(%p), kind=kLoop, calls=%cc
  %cp = f32[64]{0} collective-permute(%wrapped_convert), source_target_pairs={{0,1}}
  %native = f32[32]{0} parameter(1)
  %cp2 = f32[32]{0} collective-permute(%native), source_target_pairs={{0,1}}
"""
    out = collective_bytes(hlo)
    assert out["collective-permute"] == 64 * 4 + 32 * 4  # raw (normalized f32)
    assert out["wire_collective-permute"] == 64 * 2 + 32 * 4  # bf16 wire + f32
    assert out["total"] == out["wire_collective-permute"]
