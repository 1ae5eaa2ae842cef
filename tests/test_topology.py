"""Topology & mixing-matrix properties (Definition 1, Assumption 1)."""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.topology import (
    GRAPHS,
    ParticipationProcess,
    RoundRobinProcess,
    StaticProcess,
    TOPOLOGY_PROCESSES,
    Topology,
    best_constant_weights,
    edge_list,
    erdos_renyi_graph,
    expected_mixing_rate,
    global_matrix,
    is_connected,
    is_doubly_stochastic,
    make_topology,
    make_topology_process,
    metropolis_weights,
    mixing_rate,
    ring_graph,
    second_singular_value,
    torus_graph,
)
from repro.utils.pytree import tree_agent_masked_mean


@pytest.mark.parametrize("name", ["ring", "path", "star", "full"])
@pytest.mark.parametrize("n", [2, 4, 10, 16])
@pytest.mark.parametrize("weighting", ["metropolis", "best_constant"])
def test_doubly_stochastic(name, n, weighting):
    topo = make_topology(name, n, weighting)
    assert is_doubly_stochastic(topo.w)


@given(n=st.integers(3, 24), prob=st.floats(0.05, 0.9), seed=st.integers(0, 100))
@settings(max_examples=40, deadline=None)
def test_er_metropolis_doubly_stochastic(n, prob, seed):
    adj = erdos_renyi_graph(n, prob, seed)
    w = metropolis_weights(adj)
    assert is_doubly_stochastic(w)
    lam = mixing_rate(w)
    assert 0.0 <= lam <= 1.0 + 1e-9
    # disconnected graphs must have lambda_w == 0 (Definition 1)
    if not is_connected(adj):
        assert lam == pytest.approx(0.0, abs=1e-9)


@given(n=st.integers(3, 20), seed=st.integers(0, 50))
@settings(max_examples=25, deadline=None)
def test_contraction_property(n, seed):
    """||Wx - x_bar||^2 <= (1 - lambda_w) ||x - x_bar||^2 (paper §2.1)."""
    rng = np.random.default_rng(seed)
    topo = make_topology("ring", n)
    x = rng.normal(size=(n, 3))
    xbar = x.mean(axis=0, keepdims=True)
    lhs = np.sum((topo.w @ x - xbar) ** 2)
    rhs = (1.0 - topo.lambda_w) * np.sum((x - xbar) ** 2)
    assert lhs <= rhs + 1e-9


def test_global_matrix_is_projection():
    j = global_matrix(7)
    assert np.allclose(j @ j, j)
    assert mixing_rate(j) == pytest.approx(1.0)


def test_expected_mixing_rate_formula():
    # Assumption 1: lambda_p = lambda_w + p (1 - lambda_w)
    assert expected_mixing_rate(0.0, 0.5) == pytest.approx(0.5)
    assert expected_mixing_rate(0.3, 0.0) == pytest.approx(0.3)
    assert expected_mixing_rate(0.3, 1.0) == pytest.approx(1.0)
    topo = make_topology("ring", 10)
    assert topo.expected_rate(0.1) == pytest.approx(
        topo.lambda_w + 0.1 * (1 - topo.lambda_w)
    )


def test_disconnected_has_zero_rate_and_connected_flag():
    topo = make_topology("disconnected", 12, n_components=3)
    assert not topo.connected
    assert topo.lambda_w == pytest.approx(0.0, abs=1e-9)


def test_ring_detected_as_circulant():
    topo = make_topology("ring", 8)
    assert topo.shifts is not None
    shifts = dict((s, w) for s, w in topo.shifts)
    assert 1 in shifts and (8 - 1) in shifts or -1 in shifts


def test_torus_shapes():
    adj = torus_graph(4, 4)
    assert adj.sum(axis=1).min() == 4  # every node has 4 neighbors
    topo = make_topology("torus", 16, rows=4)
    assert is_doubly_stochastic(topo.w)
    assert topo.connected


def test_path_worse_than_ring():
    ring = make_topology("ring", 16)
    path = make_topology("path", 16)
    full = make_topology("full", 16)
    assert path.lambda_w < ring.lambda_w < full.lambda_w


def test_best_constant_on_ring_beats_or_matches_metropolis():
    ring_m = make_topology("ring", 16, "metropolis")
    ring_b = make_topology("ring", 16, "best_constant")
    assert ring_b.lambda_w >= ring_m.lambda_w - 1e-9


# ---------------------------------------------------------------------------
# Dynamic networks: TopologyProcess realizations (property-based)
# ---------------------------------------------------------------------------

PROCESS_SPECS = ["static", "bernoulli:0.3", "matching", "roundrobin:2"]
PROCESS_NS = [1, 2, 3, 8, 16]


@given(
    n=st.sampled_from(PROCESS_NS),
    spec=st.sampled_from(PROCESS_SPECS),
    k=st.integers(0, 40),
    seed=st.integers(0, 20),
)
@settings(max_examples=60, deadline=None)
def test_process_realizations_are_valid_mixing_matrices(n, spec, k, seed):
    """Every realized W_k is symmetric, doubly stochastic, supported only on
    base-graph edges, and satisfies its own §2.1 contraction bound."""
    base = make_topology("ring", n)
    proc = make_topology_process(spec, base, seed=seed)
    w = proc.weights_at(k)
    assert w.shape == (n, n)
    assert is_doubly_stochastic(w)
    assert np.allclose(w, w.T)
    off_support = (np.abs(w) > 1e-12) & ~np.eye(n, dtype=bool)
    assert not np.any(off_support & ~base.adj), "gossip over a non-edge"
    lam = mixing_rate(w)
    assert -1e-9 <= lam <= 1.0 + 1e-9
    # per-realization contraction at the realization's own rate
    rng = np.random.default_rng(seed + 1)
    x = rng.normal(size=(n, 3))
    xbar = x.mean(axis=0, keepdims=True)
    lhs = np.sum((w @ x - xbar) ** 2)
    rhs = (1.0 - lam) * np.sum((x - xbar) ** 2)
    assert lhs <= rhs + 1e-9


@given(
    n=st.sampled_from([2, 3, 8, 16]),
    spec=st.sampled_from(PROCESS_SPECS),
    seed=st.integers(0, 10),
)
@settings(max_examples=30, deadline=None)
def test_process_time_average_mixes_at_least_as_well_as_worst_draw(n, spec, seed):
    """||mean_t W_t - J|| <= max_t ||W_t - J|| (convexity of the operator
    norm), i.e. the time-averaged matrix's mixing rate is bounded below by
    the worst single realization — the dynamic analogue of the static bound.
    For the static process this is equality with the base graph's rate."""
    base = make_topology("ring", n)
    proc = make_topology_process(spec, base, seed=seed)
    draws = [proc.weights_at(k) for k in range(12)]
    w_bar = np.mean(draws, axis=0)
    worst = max(second_singular_value(w) for w in draws)
    assert second_singular_value(w_bar) <= worst + 1e-9
    assert mixing_rate(w_bar) >= min(mixing_rate(w) for w in draws) - 1e-9
    if spec == "static":
        assert mixing_rate(w_bar) == pytest.approx(base.lambda_w, abs=1e-9)


def test_static_process_reproduces_base_topology():
    base = make_topology("ring", 8, "best_constant")
    proc = make_topology_process("static", base)
    assert isinstance(proc, StaticProcess) and proc.static
    for k in (0, 3, 17):
        np.testing.assert_array_equal(proc.weights_at(k), base.w)
        assert proc.messages_at(k) == int(base.adj.sum())


def test_bernoulli_process_failure_prob_limits():
    base = make_topology("ring", 8)
    keep_all = make_topology_process("bernoulli:0.0", base, seed=1)
    drop_all = make_topology_process("bernoulli:1.0", base, seed=1)
    for k in range(4):
        np.testing.assert_array_equal(keep_all.adjacency_at(k), base.adj)
        assert drop_all.messages_at(k) == 0
        np.testing.assert_array_equal(drop_all.weights_at(k), np.eye(8))


def test_matching_process_realizes_disjoint_pairs():
    base = make_topology("full", 8)
    proc = make_topology_process("matching", base, seed=3)
    for k in range(6):
        edges = proc.edges_at(k)
        flat = edges.ravel()
        assert len(flat) == len(set(flat.tolist())), "agent in two pairs"
        # maximal on the complete graph: n/2 pairs
        assert len(edges) == 4
        w = proc.weights_at(k)
        matched = sorted(flat.tolist())
        for i, j in edges:
            assert w[i, j] == pytest.approx(0.5)


def test_roundrobin_cycle_covers_every_base_edge_once():
    base = make_topology("ring", 10)
    proc = make_topology_process("roundrobin:3", base, seed=0)
    assert isinstance(proc, RoundRobinProcess)
    union = np.zeros_like(base.adj)
    total_edges = 0
    for k in range(3):
        union |= proc.adjacency_at(k)
        total_edges += len(proc.edges_at(k))
    np.testing.assert_array_equal(union, base.adj)
    assert total_edges == int(base.adj.sum()) // 2
    # deterministic cycle
    np.testing.assert_array_equal(proc.weights_at(0), proc.weights_at(3))


@given(spec=st.sampled_from(PROCESS_SPECS), seed=st.integers(0, 10))
@settings(max_examples=20, deadline=None)
def test_process_draws_are_pure_functions_of_seed_and_round(spec, seed):
    """Block draws equal per-round draws and re-instantiation: the contract
    that makes the loop and scan drivers agree under any block boundaries."""
    base = make_topology("ring", 8)
    p1 = make_topology_process(spec, base, seed=seed)
    p2 = make_topology_process(spec, base, seed=seed)
    edge_w, self_w, msgs = p1.draw_sparse_block(2, 7)
    e = edge_list(base.adj)
    assert edge_w.shape == (5, len(e)) and self_w.shape == (5, 8)
    for i, k in enumerate(range(2, 7)):
        w = np.diag(self_w[i])
        w[e[:, 0], e[:, 1]] = w[e[:, 1], e[:, 0]] = edge_w[i]
        np.testing.assert_allclose(w, p2.weights_at(k).astype(np.float32), atol=1e-7)
        assert msgs[i] == p2.messages_at(k)


def test_make_topology_process_rejects_unknown_kind():
    base = make_topology("ring", 4)
    with pytest.raises(ValueError, match="unknown topology process"):
        make_topology_process("smallworld", base)
    assert set(TOPOLOGY_PROCESSES) == {
        "static", "bernoulli", "matching", "roundrobin", "cohort"
    }


# ---------------------------------------------------------------------------
# Partial participation
# ---------------------------------------------------------------------------


@given(
    n=st.sampled_from(PROCESS_NS),
    frac=st.floats(0.1, 1.0),
    k=st.integers(0, 20),
    seed=st.integers(0, 10),
)
@settings(max_examples=40, deadline=None)
def test_participation_matrix_is_doubly_stochastic_sampling(n, frac, k, seed):
    proc = ParticipationProcess(n, frac, seed=seed)
    assert 1 <= proc.m <= n
    part = proc.participants_at(k)
    assert len(part) == proc.m == len(set(part.tolist()))
    s = proc.server_matrix_at(k)
    assert is_doubly_stochastic(s)
    assert np.allclose(s, s.T)
    # participants average among themselves, absentees hold
    absent = np.setdiff1d(np.arange(n), part)
    for i in absent:
        assert s[i, i] == pytest.approx(1.0)
    x = np.random.default_rng(seed).normal(size=(n, 2))
    np.testing.assert_allclose((s @ x).mean(axis=0), x.mean(axis=0), atol=1e-12)


def test_participation_draws_are_deterministic_and_vary_by_round():
    p1 = ParticipationProcess(16, 0.25, seed=4)
    p2 = ParticipationProcess(16, 0.25, seed=4)
    masks, counts = p1.draw_mask_block(0, 8)
    assert counts.tolist() == [4] * 8
    sets = set()
    x = np.random.default_rng(0).normal(size=(16, 3)).astype(np.float32)
    for i in range(8):
        np.testing.assert_array_equal(np.flatnonzero(masks[i]), p2.participants_at(i))
        np.testing.assert_allclose(
            np.asarray(tree_agent_masked_mean(x, masks[i])),
            p2.server_matrix_at(i) @ x, rtol=1e-6, atol=1e-6,
        )
        sets.add(tuple(p2.participants_at(i).tolist()))
    assert len(sets) > 1, "participation never resampled"
