"""Baseline algorithms: convergence + structural properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_logreg_problem
from repro.core import (
    PiscoConfig,
    make_sparse_topology,
    replicate_params,
    run_training,
    sparse_mixing,
)
from repro.core.baselines import BASELINES


@pytest.mark.parametrize(
    "algo", ["dsgd", "dsgt", "gossip_pga", "fedavg", "scaffold", "periodical_gt"]
)
def test_baseline_converges(algo):
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.15, eta_c=1.0, p=0.1, seed=0)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    hist = run_training(
        algo, loss_fn, x0, cfg, mixing, sampler_factory(cfg.t_o),
        rounds=50,
        eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)},
        eval_every=10,
    )
    assert np.isfinite(hist.loss).all()
    assert hist.eval_metrics[-1]["grad_sq"] < 0.2
    assert hist.loss[-1] < hist.loss[0]


def test_gossip_pga_schedule_is_periodic():
    n = 4
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=1, eta_l=0.1, p=0.25, seed=0)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    hist = run_training(
        "gossip_pga", loss_fn, x0, cfg, mixing, sampler_factory(1), rounds=12
    )
    # p=0.25 -> period 4: rounds 3, 7, 11 are global
    assert hist.is_global == [(k + 1) % 4 == 0 for k in range(12)]


def test_fedavg_always_server():
    n = 4
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, p=0.0, seed=0)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    hist = run_training(
        "fedavg", loss_fn, x0, cfg, mixing, sampler_factory(2), rounds=8
    )
    assert hist.accountant.agent_to_server == 8
    assert hist.accountant.agent_to_agent == 0


def test_scaffold_control_variates_average_to_server_variate():
    """After each SCAFFOLD round, c == mean_i(c_i) (server aggregation)."""
    from repro.core.baselines import make_scaffold_round_fn, scaffold_init

    n = 6
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    mixing = sparse_mixing(make_sparse_topology("full", n))
    sampler = sampler_factory(2)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    state = scaffold_init(loss_fn, x0, sampler(-1)[1])
    fn = jax.jit(make_scaffold_round_fn(loss_fn, 0.1, 1.0, 2, mixing))
    for k in range(3):
        state, _ = fn(state, *sampler(k))
    c_bar = jnp.mean(state.c_i["w"], axis=0)
    assert float(jnp.max(jnp.abs(state.c["w"] - c_bar[None]))) < 1e-6


def test_dsgt_matches_decentralized_structure():
    """DSGT state trees keep the tracking invariant mean(y)=mean(g)."""
    from repro.core.baselines import dsgt_init, make_dsgt_round_fn

    n = 6
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    sampler = sampler_factory(1)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    state = dsgt_init(loss_fn, x0, sampler(-1)[1])
    fn = jax.jit(make_dsgt_round_fn(loss_fn, 0.1, mixing))
    for k in range(4):
        state, _ = fn(state, *sampler(k))
    ybar = jnp.mean(state.y["w"], axis=0)
    gbar = jnp.mean(state.g["w"], axis=0)
    assert float(jnp.max(jnp.abs(ybar - gbar))) < 1e-5


def test_registry_covers_everything():
    assert set(BASELINES) == {
        "dsgd", "gossip_pga", "dsgt", "periodical_gt", "fedavg", "scaffold", "pisco",
    }
