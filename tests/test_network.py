"""Conformance tests for the dynamic-network protocol stack.

Composes the PR-1 compressed-gossip subsystem with time-varying topologies
and partial participation, and checks the invariants the whole stack rests
on: Lemma 1 (mean tracking) under sampled links, realized-edge byte
accounting against hand-computed counts, and seed determinism of the
``network=`` ExperimentSpec field across drivers and serialization.
"""
import json
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_logreg_problem, reference_network_mixing
from repro.core import Experiment, ExperimentSpec, message_bytes

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


# ---------------------------------------------------------------------------
# Gradient-tracking invariant (Lemma 1) under sampled links x compression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compression", [None, "q8", "top0.3"])
@pytest.mark.parametrize("network", ["bernoulli:0.4", "matching"])
def test_gt_invariant_survives_sampled_links_and_compression(network, compression):
    """mean(Y) == mean(G) after rounds of sampled-link (and compressed)
    gossip plus partially-participating server rounds: every realized W_k and
    S_k is doubly stochastic and the difference-form compressed gossip
    preserves the agent mean, so the Lemma-1 identity must hold exactly
    (up to float32 accumulation)."""
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
# Realized-edge / realized-participant byte accounting
# ---------------------------------------------------------------------------


def test_realized_gossip_bytes_match_hand_computed_edge_count():
    """roundrobin:2 on a 4-ring realizes exactly 2 of the 4 base edges per
    round — the accountant must charge PISCO's two mixes over 4 directed
    messages, not the static graph's 8."""
    n, rounds = 4, 4
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=n, t_o=1, eta_l=0.1, p=0.0, seed=0,
        network="roundrobin:2", rounds=rounds, driver="scan", block_size=2,
    )
    exp = _experiment(spec, n=n)
    hist = exp.run()
    d = 16  # make_logreg_problem feature dim
    msg = d * 4  # one fp32 message per agent
    assert hist.byte_model.gossip_message_bytes == msg
    per_round = 2 * (2 * 2) * msg  # 2 mixes x (2 realized edges x 2 dirs)
    assert hist.accountant.per_round_bytes == [per_round] * rounds
    assert hist.accountant.agent_to_agent_bytes == rounds * per_round
    assert hist.accountant.agent_to_server_bytes == 0
    # the static model would have priced the full ring (4 edges): 2x more
    assert hist.byte_model.gossip_round_bytes == 2 * per_round


def test_realized_server_bytes_price_sampled_participants():
    """participation=0.5 on 4 agents samples m=2: a server round moves
    2 uploads + 2 downloads of PISCO's two payloads, not 4+4."""
    n, rounds = 4, 3
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=n, t_o=1, eta_l=0.1, p=1.0, seed=0,
        network="static", participation=0.5, rounds=rounds,
        driver="scan", block_size=2,
    )
    hist = _experiment(spec, n=n).run()
    msg = 16 * 4
    per_round = 2 * 2 * 2 * msg  # server_payloads x 2 dirs x m participants
    assert hist.accountant.per_round_bytes == [per_round] * rounds
    assert hist.accountant.agent_to_server_bytes == rounds * per_round
    # full participation would have cost n/m = 2x more per round
    assert hist.byte_model.server_round_bytes == 2 * per_round


def test_joint_compression_dynamic_participation_bytes_hand_counted():
    """The three pricing paths *composed* — q8 gossip compression x
    roundrobin:2 link cycling x m-of-n participation — against fully
    hand-counted per-round charges.

    On a 4-ring (base edges (0,1),(0,3),(1,2),(2,3)), roundrobin:2 realizes
    exactly 2 edges every round.  A q8 message for the d=16 problem is
    16x8 + 32 scale bits = 20 bytes; a full-precision server message is
    64 bytes.  PISCO mixes two streams (X and Y) and ships two payloads per
    server direction, and participation=0.5 samples m=2 of 4 agents:

      gossip round: 2 mixes x (2 edges x 2 dirs) x 20 B          = 160 B
      server round: 2 payloads x 2 dirs x 2 participants x 64 B  = 512 B
    """
    n, rounds = 4, 6
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=n, t_o=1, eta_l=0.1, p=0.5, seed=3,
        network="roundrobin:2", participation=0.5, compression="q8",
        rounds=rounds, driver="scan", block_size=2,
    )
    hist = _experiment(spec, n=n).run()
    assert hist.byte_model.gossip_message_bytes == 20
    assert hist.byte_model.server_message_bytes == 64
    expected = [512 if g else 160 for g in hist.is_global]
    assert hist.accountant.per_round_bytes == expected
    n_srv = sum(hist.is_global)
    assert 0 < n_srv < rounds  # p=0.5/seed=3 realizes both round kinds
    assert hist.accountant.agent_to_server_bytes == 512 * n_srv
    assert hist.accountant.agent_to_agent_bytes == 160 * (rounds - n_srv)
    # identical charges under the legacy loop driver (same pure draws)
    h_loop = _experiment(spec.replace(driver="loop"), n=n).run()
    assert h_loop.accountant.per_round_bytes == expected


def test_static_process_bytes_and_losses_match_legacy_dense_path():
    """network='static' runs through the dynamic machinery but must realize
    the same weights and the same per-round bytes as the frozen-weight path
    (network=None), and both must match the dense frozen-W reference."""
    base_kw = dict(
        algo="dsgt", n_agents=N_AGENTS, t_o=1, eta_l=0.1, p=0.3, seed=1,
        rounds=7, driver="scan", block_size=3,
    )
    legacy = ExperimentSpec.create(**base_kw)
    h_legacy = _experiment(legacy).run()
    h_static = _experiment(
        ExperimentSpec.create(network="static", **base_kw)
    ).run()
    h_dense = _experiment(legacy, mixing=reference_network_mixing(legacy)).run()
    for h in (h_static, h_dense):
        assert h_legacy.is_global == h.is_global
        assert h_legacy.accountant.per_round_bytes == h.accountant.per_round_bytes
        np.testing.assert_allclose(h_legacy.loss, h.loss, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# Seed determinism + spec round-trips
# ---------------------------------------------------------------------------


def test_network_spec_round_trips_and_reproduces_history_exactly():
    """A spec with network/participation fields survives dict / JSON / pickle
    round-trips, and every round-tripped copy reproduces a byte-identical
    History under both drivers (same seeds => same realized links,
    participants, schedule, and floats)."""
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=N_AGENTS, t_o=2, eta_l=0.15, p=0.3, seed=5,
        network="bernoulli:0.35", participation=0.6,
        rounds=8, eval_every=4, block_size=3,
    )
    copies = [
        ExperimentSpec.from_dict(spec.to_dict()),
        ExperimentSpec.from_json(spec.to_json()),
        pickle.loads(pickle.dumps(spec)),
    ]
    for c in copies:
        assert c == spec
    payload = json.loads(spec.to_json())
    assert payload["network"] == "bernoulli:0.35"
    assert payload["participation"] == 0.6

    for driver in ("loop", "scan"):
        ref = _experiment(spec.replace(driver=driver)).run()
        for c in copies:
            rerun = _experiment(c.replace(driver=driver)).run()
            assert rerun.is_global == ref.is_global
            assert rerun.loss == ref.loss  # bitwise: same program, same draws
            assert rerun.grad_sq_norm == ref.grad_sq_norm
            assert (
                rerun.accountant.per_round_bytes
                == ref.accountant.per_round_bytes
            )


def test_sweep_seeds_threads_dynamic_network_operands():
    """The vmapped multi-seed sweep advances every seed through the same
    realized network (matrices broadcast over the seed axis); the seed whose
    data sampler matches a solo run must reproduce it."""
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=4)
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=4, t_o=1, eta_l=0.1, p=0.4, seed=0,
        network="matching", participation=0.5,
        rounds=6, driver="scan", block_size=3,
    )
    factory = lambda s: sampler_factory(s.config.t_o, seed=s.config.seed)
    exp = Experiment(
        spec, loss_fn=loss_fn, params0={"w": jnp.zeros(d)},
        sampler_factory=factory,
    )
    swept = exp.sweep(seeds=[0, 1])
    solo = Experiment(
        spec, loss_fn=loss_fn, params0={"w": jnp.zeros(d)},
        sampler_factory=factory,
    ).run()
    # seed 0 shares the spec's schedule/network/data seeds with the solo run
    assert swept[0].is_global == solo.is_global
    np.testing.assert_allclose(swept[0].loss, solo.loss, rtol=1e-5, atol=1e-6)
    for hist in swept:
        assert len(hist.loss) == 6 and np.isfinite(hist.loss).all()
        # realized charges are a network property: identical across seeds
        assert (
            hist.accountant.per_round_bytes
            == solo.accountant.per_round_bytes
        )


def test_participation_validation():
    with pytest.raises(ValueError, match="participation"):
        ExperimentSpec.create(algo="pisco", n_agents=4, participation=0.0)
    with pytest.raises(ValueError, match="participation"):
        ExperimentSpec.create(algo="pisco", n_agents=4, participation=1.5)


def test_network_spec_validated_at_construction():
    """Typos fail when the spec is built, not mid-run inside make_mixing."""
    with pytest.raises(ValueError, match="unknown topology process"):
        ExperimentSpec.create(algo="pisco", n_agents=4, network="bernouli:0.3")
    with pytest.raises(ValueError, match="failure prob"):
        ExperimentSpec.create(algo="pisco", n_agents=4, network="bernoulli:1.5")
    with pytest.raises(ValueError, match="takes no argument"):
        ExperimentSpec.create(algo="pisco", n_agents=4, network="matching:3")


def test_old_spec_payloads_still_load():
    """Pre-dynamic JSON payloads (no network/participation keys) deserialize
    to the legacy static behavior."""
    spec = ExperimentSpec.create(algo="dsgd", n_agents=4, p=0.0, rounds=5)
    d = spec.to_dict()
    d.pop("network")
    d.pop("participation")
    old = ExperimentSpec.from_dict(d)
    assert old.network is None and old.participation == 1.0
    assert old == spec
