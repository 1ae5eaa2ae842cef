"""Conformance suite for edge-list gossip, the one gossip operand.

Every mixer gossips from the edge list; it must be indistinguishable from
the dense ``W @ x`` reference (``conftest.reference_mixing``) wherever a
dense W can be built: the same Metropolis weights, every named topology,
the same training trajectories for every registered protocol under both
drivers, the same realized byte charges, and the same Lemma-1
mean-tracking invariant under compression.  Property tests run through the
optional-hypothesis shim (``tests/_hyp.py``), so they degrade to
deterministic fixed examples when hypothesis is absent.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import (
    make_logreg_problem,
    reference_mixing,
    reference_network_mixing,
)
from repro.core import (
    Experiment,
    ExperimentSpec,
    PiscoConfig,
    dynamic_sparse_mixing,
    is_doubly_stochastic,
    make_sparse_topology,
    make_topology,
    make_topology_process,
    metropolis_edge_weights,
    registered_algorithms,
    replicate_params,
    run_training,
    sparse_mixing,
)
from repro.core.mixing import sparse_gossip
from repro.core.topology import metropolis_weights, sparse_topology_from_edges
from repro.utils.pytree import tree_agent_mix_sparse

N_AGENTS = 5


def _experiment(spec, n=N_AGENTS, mixing=None):
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    return Experiment(
        spec,
        loss_fn=loss_fn,
        params0={"w": jnp.zeros(d)},
        sampler_factory=lambda s: sampler_factory(s.config.t_o),
        mixing=mixing,
    )


def _random_connected_edges(n, seed, extra_prob=0.3):
    """Ring ∪ Erdős–Rényi: always connected, random beyond the ring."""
    rng = np.random.default_rng(seed)
    edges = {(i, (i + 1) % n) if i < (i + 1) % n else ((i + 1) % n, i)
             for i in range(n) if n > 1}
    for i in range(n):
        for j in range(i + 1, n):
            if rng.random() < extra_prob:
                edges.add((i, j))
    return np.array(sorted(edges), dtype=np.int64).reshape(-1, 2)


# ---------------------------------------------------------------------------
# Property: sparse gossip over the edge list == dense W @ X
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    n=st.sampled_from([2, 8, 64]),
    seed=st.integers(min_value=0, max_value=5),
    cols=st.sampled_from([1, 7]),
)
def test_sparse_gossip_matches_dense_matrix_product(n, seed, cols):
    edges = _random_connected_edges(n, seed)
    topo = sparse_topology_from_edges("rand", n, edges)
    w = topo.dense_w()
    assert is_doubly_stochastic(w)

    rng = np.random.default_rng(seed + 100)
    x = jnp.asarray(rng.normal(size=(n, cols)).astype(np.float32))
    senders = jnp.asarray(
        np.concatenate([edges[:, 0], edges[:, 1]]), dtype=jnp.int32
    )
    receivers = jnp.asarray(
        np.concatenate([edges[:, 1], edges[:, 0]]), dtype=jnp.int32
    )
    edge_w = jnp.asarray(np.concatenate([topo.edge_weight] * 2), jnp.float32)
    self_w = jnp.asarray(topo.self_weight, jnp.float32)

    out = tree_agent_mix_sparse(x, senders, receivers, edge_w, self_w, n)
    np.testing.assert_allclose(
        np.asarray(out), w.astype(np.float32) @ np.asarray(x),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# The offset (banded) form: gossip as weighted shifts along the agent axis
# ---------------------------------------------------------------------------

_BANDED = [("ring", 2, (3, 4)), ("ring", 64, (5,)), ("path", 64, (2, 3)),
           ("torus", 64, (4,))]


def _directed_weights(topo):
    e = topo.edges
    return (np.concatenate([e[:, 0], e[:, 1]]), np.concatenate([e[:, 1], e[:, 0]]),
            np.concatenate([topo.edge_weight, topo.edge_weight]))


@pytest.mark.parametrize("name,n,tail", _BANDED)
def test_offset_form_matches_dense_matrix_product(name, n, tail):
    topo = make_sparse_topology(name, n)
    mixing = sparse_mixing(topo)
    assert mixing.form.startswith("offsets/")
    x = np.random.default_rng(n).normal(size=(n,) + tail).astype(np.float32)
    out = jax.jit(mixing.gossip)({"a": jnp.asarray(x)})["a"]
    ref = np.tensordot(topo.dense_w().astype(np.float32), x, axes=1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name,n,tail", _BANDED)
def test_offset_form_with_dropped_edges_matches_dense(name, n, tail):
    """Zeros in a traced ``edge_w`` (as the dynamic and events drivers stage
    them) leave their slots empty: the result is the dropped-edge W @ x."""
    topo = make_sparse_topology(name, n)
    form, mix = sparse_gossip(topo)
    assert form.startswith("offsets/")
    senders, receivers, edge_w = _directed_weights(topo)
    rng = np.random.default_rng(n + 1)
    keep = np.tile(rng.random(topo.n_edges) < 0.5, 2)
    edge_w = np.where(keep, edge_w, 0.0).astype(np.float32)
    self_w = rng.random(n).astype(np.float32)
    w = np.diag(self_w).astype(np.float64)
    np.add.at(w, (receivers, senders), edge_w)
    x = rng.normal(size=(n,) + tail).astype(np.float32)
    out = jax.jit(mix)(jnp.asarray(x), jnp.asarray(edge_w), jnp.asarray(self_w))
    ref = np.tensordot(w, x, axes=1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "name,n,form",
    [("ring", 2, "offsets/1"), ("ring", 64, "offsets/2"), ("path", 64, "offsets/2"),
     ("torus", 64, "offsets/6"), ("random_regular", 64, "edges"),
     ("star", 64, "edges"), ("erdos_renyi", 64, "edges")],
)
def test_sparse_gossip_form_follows_the_edge_list(name, n, form):
    topo = make_sparse_topology(name, n)
    assert sparse_mixing(topo).form == form
    process = make_topology_process("bernoulli:0.3", topo, seed=0)
    assert dynamic_sparse_mixing(process).form == form


_NAMED = ["ring", "path", "star", "full", "erdos_renyi", "disconnected", "torus",
          "random_regular"]


@pytest.mark.parametrize("n", [1, 2, 9])
@pytest.mark.parametrize("name", _NAMED)
def test_every_named_topology_mixes_as_its_w(name, n):
    """Every graph ``make_topology`` names, at every small size, gossips
    through the spec's edge-list mixer exactly as its dense W."""
    mixing = ExperimentSpec.create(n_agents=n, topology=name).make_mixing()
    assert mixing.form is not None
    x = np.random.default_rng(n).normal(size=(n, 3, 2)).astype(np.float32)
    out = jax.jit(mixing.gossip)({"a": jnp.asarray(x)})["a"]
    ref = np.tensordot(make_topology(name, n).w, x.astype(np.float64), axes=1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("via", ["create", "from_dict"])
@pytest.mark.parametrize("flag", [True, False, None])
def test_spec_accepts_and_drops_the_sparse_key(flag, via):
    """The retired ``sparse`` key (older callers and JSON payloads) is
    accepted and dropped: whatever it held, the same edge-list mixer."""
    kw = dict(algo="pisco", n_agents=6, p=0.2, network="bernoulli:0.3", rounds=3)
    plain = ExperimentSpec.create(**kw)
    if via == "create":
        spec = ExperimentSpec.create(sparse=flag, **kw)
    else:
        spec = ExperimentSpec.from_dict(dict(plain.to_dict(), sparse=flag))
    assert spec == plain and "sparse" not in spec.to_dict()
    got, want = spec.make_mixing(), plain.make_mixing()
    assert (got.name, got.form, got.gossip_edges) == (want.name, want.form, want.gossip_edges)
    x = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(6, 4)), jnp.float32)}
    ops = jax.tree.map(lambda a: a[0], got.network.draw_block(2, 3)[0])
    got.network.slot.set(ops, None)
    want.network.slot.set(ops, None)
    np.testing.assert_array_equal(np.asarray(got.gossip(x)["w"]),
                                  np.asarray(want.gossip(x)["w"]))


def test_ring_gossip_lowers_without_gather_or_scatter():
    gossip = sparse_mixing(make_sparse_topology("ring", 64)).gossip
    x = {"w": jnp.zeros((64, 7, 3)), "b": jnp.zeros((64, 3))}
    lowered = jax.jit(gossip).lower(x)
    assert not re.search(r"stablehlo\.(gather|scatter)", lowered.as_text())
    assert not re.search(r"\b(gather|scatter)\(", lowered.compile().as_text())


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([2, 8, 64]), seed=st.integers(min_value=0, max_value=5))
def test_edge_metropolis_matches_dense_metropolis(n, seed):
    """The O(n+m) degree-array construction and the dense n×n construction
    are the same Metropolis–Hastings matrix."""
    edges = _random_connected_edges(n, seed)
    adj = np.zeros((n, n), bool)
    adj[edges[:, 0], edges[:, 1]] = True
    adj[edges[:, 1], edges[:, 0]] = True
    dense = metropolis_weights(adj)

    edge_w, self_w = metropolis_edge_weights(edges, n)
    rebuilt = np.zeros((n, n))
    rebuilt[edges[:, 0], edges[:, 1]] = edge_w
    rebuilt[edges[:, 1], edges[:, 0]] = edge_w
    np.fill_diagonal(rebuilt, self_w)
    np.testing.assert_allclose(rebuilt, dense, rtol=0, atol=1e-12)


@pytest.mark.parametrize("name", ["ring", "path", "star", "torus", "random_regular"])
@pytest.mark.parametrize("n", [2, 9, 64])
def test_sparse_topology_pins_dense_topology_small_n(name, n):
    if name == "torus" and n == 2:
        pytest.skip("torus needs a 2d grid")
    dense = make_topology(name, n, seed=3)
    sparse = make_sparse_topology(name, n, seed=3)
    np.testing.assert_allclose(sparse.dense_w(), dense.w, rtol=0, atol=1e-12)
    assert sparse.connected == dense.connected
    if sparse.lambda_w is not None:
        np.testing.assert_allclose(sparse.lambda_w, dense.lambda_w, atol=1e-9)


# ---------------------------------------------------------------------------
# is_doubly_stochastic at scale (the tol=1e-8 bugfix)
# ---------------------------------------------------------------------------


def test_doubly_stochastic_tolerance_scales_with_n():
    """A float32 Metropolis matrix at n ≥ 4096 accumulates ~1e-7 of row-sum
    error — legitimately doubly stochastic, yet the historical fixed
    tol=1e-8 (now honestly enforced with rtol=0) rejects it.  The scaled
    default accepts it while still rejecting genuinely broken matrices.
    (The sparse constructor is used directly: make_topology would spend
    minutes on the n² spectral-gap eigendecomposition this test does not
    need.)"""
    n = 4096
    topo = make_sparse_topology("random_regular", n, seed=0, degree=6)
    w = topo.dense_w().astype(np.float32)
    row_err = float(np.abs(w.sum(axis=1) - 1.0).max())
    assert row_err > 1e-8  # float32 rounding actually materialized
    assert is_doubly_stochastic(w)  # scaled default: accepted
    assert not is_doubly_stochastic(w, tol=1e-8)  # the old bug, now honest

    bad = w.copy()
    bad[0, 0] += 0.01
    assert not is_doubly_stochastic(bad)


# ---------------------------------------------------------------------------
# Full-protocol parity: edge-list mixers vs the dense reference, both drivers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", registered_algorithms())
def test_sparse_training_matches_dense_all_protocols(algo):
    n, rounds = 6, 6
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.15, eta_c=1.0, p=0.3, seed=0)

    def run(mixing, driver):
        return run_training(
            loss_fn=loss_fn, algo=algo, x0_stacked=x0, cfg=cfg, mixing=mixing,
            sampler=sampler_factory(cfg.t_o), rounds=rounds,
            driver=driver, block_size=3,
        )

    for driver in ("scan", "loop"):
        hd = run(reference_mixing(make_topology("ring", n)), driver)
        hs = run(sparse_mixing(make_sparse_topology("ring", n)), driver)
        np.testing.assert_allclose(hd.loss, hs.loss, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            hd.consensus_err, hs.consensus_err, rtol=1e-4, atol=1e-6
        )
        assert hd.accountant.agent_to_agent_bytes == \
            hs.accountant.agent_to_agent_bytes
        np.testing.assert_allclose(
            np.asarray(hd.final_state.x["w"]),
            np.asarray(hs.final_state.x["w"]),
            rtol=1e-4, atol=1e-6,
        )


@pytest.mark.parametrize("network", [None, "bernoulli:0.4", "cohort:0.5"])
def test_sparse_experiment_spec_matches_dense(network):
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=N_AGENTS, t_o=2, eta_l=0.1, p=0.3, seed=2,
        network=network, rounds=6, driver="scan", block_size=3,
    )
    hd = _experiment(spec, mixing=reference_network_mixing(spec)).run()
    hs = _experiment(spec).run()
    np.testing.assert_allclose(hd.loss, hs.loss, rtol=1e-5, atol=1e-6)
    assert hd.accountant.per_round_bytes == hs.accountant.per_round_bytes


# ---------------------------------------------------------------------------
# Lemma-1 invariant under sparse sampled links x compression x participation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compression", ["q8", "top0.3"])
@pytest.mark.parametrize("network", ["bernoulli:0.4", "cohort:0.5"])
def test_gt_invariant_on_sparse_path(network, compression):
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=N_AGENTS, t_o=2, eta_l=0.1, p=0.3, seed=2,
        network=network, participation=0.6, compression=compression,
        rounds=8, eval_every=4, driver="scan", block_size=3,
    )
    hist = _experiment(spec).run()
    state = hist.final_state
    assert state is not None and np.isfinite(hist.loss).all()
    y_bar = np.asarray(jnp.mean(state.y["w"], axis=0))
    g_bar = np.asarray(jnp.mean(state.g["w"], axis=0))
    scale = max(1.0, float(np.abs(g_bar).max()))
    np.testing.assert_allclose(y_bar, g_bar, atol=2e-5 * scale)


# ---------------------------------------------------------------------------
# Byte accounting: edge-list operands priced identically to dense W_k
# ---------------------------------------------------------------------------


def test_sparse_realized_gossip_bytes_match_hand_count():
    """roundrobin:2 on a 4-ring realizes 2 of 4 base edges per round; the
    accountant must charge the same 2 mixes x 4 directed messages as the
    dense reference — the wire does not care about the W representation."""
    n, rounds = 4, 4
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=n, t_o=1, eta_l=0.1, p=0.0, seed=0,
        network="roundrobin:2", rounds=rounds, driver="scan", block_size=2,
    )
    hist = _experiment(spec, n=n).run()
    msg = 16 * 4  # one fp32 message of the d=16 problem
    per_round = 2 * (2 * 2) * msg  # 2 mixes x (2 realized edges x 2 dirs)
    assert hist.accountant.per_round_bytes == [per_round] * rounds
    assert hist.accountant.agent_to_agent_bytes == rounds * per_round
    h_dense = _experiment(spec, n=n, mixing=reference_network_mixing(spec)).run()
    assert h_dense.accountant.per_round_bytes == \
        hist.accountant.per_round_bytes


# ---------------------------------------------------------------------------
# Cohort sugar + spec serialization
# ---------------------------------------------------------------------------


def test_cohort_field_expands_to_network_spec():
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=8, t_o=1, eta_l=0.1, p=0.3,
        cohort=0.25, rounds=2,
    )
    assert spec.effective_network == "cohort:0.25"
    with pytest.raises(ValueError, match="cohort"):
        ExperimentSpec.create(
            algo="pisco", n_agents=8, t_o=1, eta_l=0.1, p=0.3,
            cohort=0.25, network="static", rounds=2,
        )


def test_cohort_process_edges_are_seed_incident():
    proc = make_topology_process(
        "cohort:0.5", make_sparse_topology("ring", 8), seed=1
    )
    for k in range(4):
        seeds = set(proc.seeds_at(k))
        assert len(seeds) == 4  # ceil(0.5 * 8)
        for i, j in proc.edges_at(k):
            assert i in seeds or j in seeds


def test_spec_json_round_trip_and_legacy_payload():
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=2048, t_o=2, eta_l=0.1, p=0.1,
        cohort=0.25, rounds=4,
    )
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    # a pre-cohort-era payload (no cohort key) loads with defaults
    legacy = json.loads(spec.to_json())
    del legacy["cohort"]
    old = ExperimentSpec.from_dict(legacy)
    assert old.cohort is None
    assert old.effective_network == old.network


# ---------------------------------------------------------------------------
# Large-n smoke: the whole point of the substrate (fast-lane resident)
# ---------------------------------------------------------------------------


def test_large_n_sparse_smoke():
    """n=4096 edge-list training — a size a dense W cannot represent
    without a 67 MB mixing matrix per operand.  Deliberately NOT marked
    slow: it pins that large-n stays cheap enough for the CI fast lane."""
    n, d, rounds = 4096, 4, 3
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))

    def loss_fn(params, batch):
        return 0.5 * jnp.mean((params["w"] - batch) ** 2)

    def sampler(k):
        return jnp.stack([targets, targets]), targets

    topo = make_sparse_topology("random_regular", n, seed=0, degree=4)
    # union of 2 Hamiltonian cycles: ~n·deg/2 edges minus any coincidences
    assert topo.connected and n <= topo.n_edges <= n * 2
    mixing = dynamic_sparse_mixing(
        make_topology_process("cohort:0.25", topo, seed=0)
    )
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, eta_c=1.0, p=0.2, seed=0)
    x0 = replicate_params({"w": jnp.zeros(d, jnp.float32)}, n)
    hist = run_training(
        "pisco", loss_fn, x0, cfg, mixing, sampler,
        rounds=rounds, driver="scan", block_size=rounds,
    )
    assert np.isfinite(hist.loss).all()
    assert float(hist.loss[-1]) < float(hist.loss[0])
    assert hist.final_state.x["w"].shape == (n, d)
