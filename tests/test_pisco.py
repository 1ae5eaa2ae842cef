"""PISCO algorithm tests: the paper's own invariants and guarantees.

* Lemma 1 (gradient-tracking): mean_i y_i == mean_i g_i exactly, at every
  round, for any p / T_o / topology (hypothesis-driven).
* p=1 gives exact consensus after one round (federated case, Remark 2).
* Convergence on the nonconvex-regularized logistic problem (§5.1 analogue).
* Local updates accelerate: T_o=8 reaches the threshold in fewer rounds
  than T_o=1 (Corollary 1's linear speedup, empirically).
* Semi-decentralized p>0 beats p=0 on a disconnected graph (Assumption 1).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from conftest import make_logreg_problem
from repro.core import (
    PiscoConfig,
    init_state,
    make_round_fn,
    make_sparse_topology,
    replicate_params,
    run_training,
    sparse_mixing,
)


def _tree_mean0(tree):
    return jax.tree.map(lambda v: jnp.mean(v, axis=0), tree)


def _max_abs_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(x - y))) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@given(
    t_o=st.integers(1, 5),
    p_global=st.booleans(),
    topo_name=st.sampled_from(["ring", "path", "full", "disconnected"]),
    seed=st.integers(0, 20),
)
@settings(max_examples=12, deadline=None)
def test_lemma1_tracking_invariant(t_o, p_global, topo_name, seed):
    """mean(Y) == mean(G) after any round, any mixing kind."""
    n = 8
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n, seed=seed)
    cfg = PiscoConfig(n_agents=n, t_o=t_o, eta_l=0.1, eta_c=0.9, p=0.5)
    mixing = sparse_mixing(make_sparse_topology(topo_name, n))
    sampler = sampler_factory(t_o, seed=seed)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    state = init_state(loss_fn, x0, sampler(-1)[1])
    fn = jax.jit(make_round_fn(loss_fn, cfg, mixing, global_round=p_global))
    for k in range(3):
        state, _ = fn(state, *sampler(k))
    assert _max_abs_diff(_tree_mean0(state.y), _tree_mean0(state.g)) < 1e-5


def test_federated_round_gives_exact_consensus():
    n = 6
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, eta_c=1.0, p=1.0)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    sampler = sampler_factory(2)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    state = init_state(loss_fn, x0, sampler(-1)[1])
    fn = jax.jit(make_round_fn(loss_fn, cfg, mixing, global_round=True))
    state, metrics = fn(state, *sampler(0))
    assert float(metrics.consensus_err) < 1e-12
    w = state.x["w"]
    assert float(jnp.max(jnp.abs(w - w[0:1]))) < 1e-6


def test_pisco_converges_on_logreg():
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=4, eta_l=0.2, eta_c=1.0, p=0.1, seed=0)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    hist = run_training(
        "pisco", loss_fn, x0, cfg, mixing, sampler_factory(cfg.t_o),
        rounds=60,
        eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)},
        eval_every=5,
    )
    assert hist.eval_metrics[-1]["grad_sq"] < 0.02
    assert hist.loss[-1] < hist.loss[0]


def test_local_updates_accelerate():
    """Corollary 1's T_o speedup, measured in communication rounds."""
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    rounds_needed = {}
    for t_o in (1, 8):
        cfg = PiscoConfig(n_agents=n, t_o=t_o, eta_l=0.15, eta_c=1.0, p=0.1, seed=3)
        hist = run_training(
            "pisco", loss_fn, x0, cfg, mixing, sampler_factory(t_o),
            rounds=80,
            eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)},
            eval_every=1,
        )
        r = hist.rounds_to_threshold("grad_sq", 0.03)
        rounds_needed[t_o] = r if r is not None else 10_000
    assert rounds_needed[8] < rounds_needed[1]


def test_server_rescues_disconnected_graph():
    """On a disconnected graph, p=0 stalls on heterogeneous data while a
    small p>0 still converges (the paper's Fig. 6(b) phenomenon)."""
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(
        n_agents=n, heterogeneous=True
    )
    mixing = sparse_mixing(make_sparse_topology("disconnected", n, n_components=2))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    results = {}
    for p in (0.0, 0.2):
        cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.15, eta_c=1.0, p=p, seed=1)
        hist = run_training(
            "pisco", loss_fn, x0, cfg, mixing, sampler_factory(2),
            rounds=60,
            eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)},
            eval_every=5,
        )
        results[p] = hist.eval_metrics[-1]["grad_sq"]
    assert results[0.2] < results[0.0]


def test_step_counter_and_config_helpers():
    from repro.core import decentralized_config, federated_config

    cfg = PiscoConfig(n_agents=4, p=0.3)
    assert decentralized_config(cfg).p == 0.0
    assert federated_config(cfg).p == 1.0
    with pytest.raises(AssertionError):
        PiscoConfig(n_agents=4, t_o=0)
