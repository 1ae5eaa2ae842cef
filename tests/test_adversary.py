"""Byzantine fault injection + robust aggregation conformance (DESIGN.md §14).

* Hand-computed pins for the robust primitives (trimmed mean, coordinate
  median, Krum selection) on tiny hand-built fleets.
* Property test: with f < n/2 sign-flippers, trimmed mean preserves the
  honest-agent mean within tolerance while plain mean does not.
* Lemma-1 check documenting exactly where gradient tracking's invariant
  mean(Y) == mean(G) survives (clean mean aggregation) and where it breaks
  (corrupted payloads, non-mean rules).
* Wrapper conformance: clean path is the *same object*, accounting is
  bit-identical clean vs adversarial, loop/scan drivers agree under every
  adversary kind, the events trivial path runs, specs validate and
  JSON-round-trip, History records the mask and per-agent eval series.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from conftest import make_logreg_problem, reference_mixing
from repro.core import (
    Experiment,
    ExperimentSpec,
    PiscoConfig,
    init_state,
    make_round_fn,
    make_sparse_topology,
    make_topology,
    replicate_params,
    sparse_mixing,
)
from repro.core.adversary import (
    AdversaryProcess,
    AdversarialNetwork,
    adversary_mask,
    make_adversarial_mixing,
    parse_adversary_spec,
    unwrap_network,
)
from repro.core.mixing import make_robust_agg, parse_robust_spec
from repro.data import FederatedDataset, RoundSampler
from repro.utils.pytree import (
    tree_agent_krum,
    tree_agent_mean,
    tree_agent_median,
    tree_agent_trimmed_mean,
)


def _col(values):
    """(n, 1) float32 single-leaf fleet from a value-per-agent list."""
    return {"w": jnp.asarray(values, jnp.float32).reshape(-1, 1)}


def _max_abs_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# Hand-computed pins for the robust primitives
# ---------------------------------------------------------------------------


def test_trimmed_mean_hand_pin():
    fleet = _col([1.0, 2.0, 3.0, 4.0, 100.0])
    out = tree_agent_trimmed_mean(fleet, trim=1)
    # drop {1, 100}, average {2, 3, 4} = 3, broadcast to every agent row
    np.testing.assert_allclose(np.asarray(out["w"]), 3.0)
    # trim=0 is exactly the mean
    np.testing.assert_array_equal(
        np.asarray(tree_agent_trimmed_mean(fleet, trim=0)["w"]),
        np.asarray(tree_agent_mean(fleet)["w"]),
    )


def test_trimmed_mean_is_coordinatewise():
    # per-coordinate trimming: the outlier agent differs per column
    x = jnp.asarray([[0.0, 5.0], [1.0, 6.0], [2.0, 7.0], [99.0, -99.0]])
    out = tree_agent_trimmed_mean({"w": x}, trim=1)[("w")]
    # col 0 keeps {1, 2} -> 1.5; col 1 keeps {5, 6} -> 5.5
    np.testing.assert_allclose(np.asarray(out[0]), [1.5, 5.5])


def test_median_hand_pin():
    np.testing.assert_allclose(
        np.asarray(tree_agent_median(_col([1.0, 2.0, 3.0, 4.0, 100.0]))["w"]),
        3.0,
    )
    # even fleet: midpoint interpolation
    np.testing.assert_allclose(
        np.asarray(tree_agent_median(_col([1.0, 2.0, 3.0, 10.0]))["w"]), 2.5
    )


def test_krum_hand_pin():
    # n=5, n_byz=1 -> each agent scored on its m = 5-1-2 = 2 closest peers:
    # agents at 2 and 3 tie on score 2 (peers one apart on both sides);
    # argmin takes the first, so Krum returns agent 1's submission, value 2.
    out = tree_agent_krum(_col([1.0, 2.0, 3.0, 4.0, 100.0]), n_byz=1)
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0)


def test_krum_distance_sums_across_leaves():
    # same first leaf, but a second leaf makes agent 1 an outlier — the
    # summed-across-leaves distance must move the selection to agent 2
    fleet = {
        "a": jnp.asarray([1.0, 2.0, 3.0, 4.0, 100.0]).reshape(-1, 1),
        "b": jnp.asarray([0.0, 10.0, 0.0, 0.0, 0.0]).reshape(-1, 1),
    }
    out = tree_agent_krum(fleet, n_byz=1)
    np.testing.assert_allclose(np.asarray(out["a"]), 3.0)
    np.testing.assert_allclose(np.asarray(out["b"]), 0.0)


def test_krum_returns_an_actual_submission():
    # Krum never blends: the aggregate equals some agent's full row
    rng = np.random.default_rng(3)
    fleet = {"w": jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)}
    out = np.asarray(tree_agent_krum(fleet, n_byz=2)["w"])
    rows = np.asarray(fleet["w"])
    assert any(np.array_equal(out[0], rows[i]) for i in range(6))


# ---------------------------------------------------------------------------
# Property test: trimmed mean survives a sign-flipping minority, mean does not
# ---------------------------------------------------------------------------


@given(n=st.integers(5, 12), seed=st.integers(0, 200))
@settings(max_examples=25, deadline=None)
def test_trimmed_mean_survives_signflip_minority(n, seed):
    rng = np.random.default_rng(seed)
    n_byz = int(rng.integers(1, (n - 1) // 2 + 1))  # f < n/2, at least one
    c = 5.0
    honest = c + rng.normal(size=(n, 3)) * 0.05
    byz = rng.choice(n, size=n_byz, replace=False)
    values = honest.copy()
    values[byz] = -honest[byz]  # the sign-flip attack on the wire
    honest_mean = honest[np.setdiff1d(np.arange(n), byz)].mean(axis=0)

    fleet = {"w": jnp.asarray(values, jnp.float32)}
    trimmed = np.asarray(tree_agent_trimmed_mean(fleet, trim=n_byz)["w"])[0]
    median = np.asarray(tree_agent_median(fleet)["w"])[0]
    mean = np.asarray(tree_agent_mean(fleet)["w"])[0]

    # flipped rows sit at -c, far below the honest cluster at +c: the trim
    # discards them all, so the aggregate stays inside the honest spread
    assert np.max(np.abs(trimmed - honest_mean)) < 0.5
    assert np.max(np.abs(median - honest_mean)) < 0.5
    # plain mean is contracted by ~2 * c * n_byz / n — far outside tolerance
    assert np.max(np.abs(mean - honest_mean)) > 2.0 * c * n_byz / n - 0.5


# ---------------------------------------------------------------------------
# Spec grammars: adversary + robust_agg parse and fail fast
# ---------------------------------------------------------------------------


def test_parse_adversary_spec_grammar():
    adv = parse_adversary_spec("signflip:f=0.25", n_agents=8, seed=3)
    assert (adv.kind, adv.f, adv.n_byz) == ("signflip", 0.25, 2)
    adv = parse_adversary_spec("random:f=0.1,scale=5", n_agents=10)
    assert (adv.kind, adv.scale, adv.needs_round) == ("random", 5.0, True)
    adv = parse_adversary_spec("collusion:f=0.25,target=drift", n_agents=8)
    assert adv.spec() == "collusion:f=0.25,target=drift"
    # spec() round-trips through the parser
    for s in ("signflip:f=0.2", "random:f=0.3,scale=2", "collusion:f=0.25"):
        adv = parse_adversary_spec(s, n_agents=8)
        assert parse_adversary_spec(adv.spec(), n_agents=8) == adv


@pytest.mark.parametrize("bad", [
    "omniscient:f=0.2",          # unknown kind
    "signflip:frac=0.2",         # unknown key
    "signflip:f=0",              # fraction must be in (0, 1)
    "signflip:f=1.0",
    "collusion:f=0.2,target=mean",  # only drift collusion is implemented
])
def test_parse_adversary_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_adversary_spec(bad, n_agents=8)


def test_adversary_needs_one_honest_agent():
    with pytest.raises(ValueError):
        AdversaryProcess(kind="signflip", f=0.9, n_agents=2)  # ceil = 2 of 2


def test_parse_robust_spec():
    assert parse_robust_spec("trimmed:f=0.3") == ("trimmed", 0.3)
    assert parse_robust_spec("median") == ("median", 0.2)
    assert make_robust_agg("mean", 8) is None  # clean path keeps base rule
    for bad in ("huber", "median:f=0.1", "trimmed:g=0.1", "trimmed:f=0.6"):
        with pytest.raises(ValueError):
            parse_robust_spec(bad)
    with pytest.raises(ValueError):  # n - 2*ceil(f*n) < 1: nothing left
        make_robust_agg("trimmed:f=0.45", 4)


# ---------------------------------------------------------------------------
# The adversary process: mask purity + on-device corruption
# ---------------------------------------------------------------------------


def test_mask_pure_in_seed():
    a = AdversaryProcess(kind="signflip", f=0.2, n_agents=16, seed=4)
    np.testing.assert_array_equal(a.mask(), a.mask())
    assert int(a.mask().sum()) == a.n_byz == 4
    b = AdversaryProcess(kind="signflip", f=0.2, n_agents=16, seed=5)
    assert not np.array_equal(a.mask(), b.mask())
    assert adversary_mask(None, 16) is None
    assert adversary_mask("signflip:f=0.2", 16, seed=4) == list(a.mask())


def test_signflip_corruption_rows():
    adv = AdversaryProcess(kind="signflip", f=0.25, scale=2.0, n_agents=8)
    tree = {"w": jnp.asarray(np.arange(16, dtype=np.float32).reshape(8, 2))}
    out = adv.make_corrupt()(tree, None)
    mask = adv.mask()
    np.testing.assert_array_equal(
        np.asarray(out["w"])[~mask], np.asarray(tree["w"])[~mask]
    )  # honest rows pass through bit-exactly
    np.testing.assert_array_equal(
        np.asarray(out["w"])[mask], -2.0 * np.asarray(tree["w"])[mask]
    )


def test_random_corruption_pure_in_seed_and_round():
    adv = AdversaryProcess(kind="random", f=0.25, n_agents=8, seed=9)
    tree = {"w": jnp.ones((8, 3), jnp.float32)}
    # two independently constructed closures agree bit-for-bit under jit
    c1 = jax.jit(adv.make_corrupt())
    c2 = jax.jit(AdversaryProcess(kind="random", f=0.25, n_agents=8, seed=9)
                 .make_corrupt())
    np.testing.assert_array_equal(
        np.asarray(c1(tree, 3)["w"]), np.asarray(c2(tree, 3)["w"])
    )
    mask = adv.mask()
    out3, out4 = np.asarray(c1(tree, 3)["w"]), np.asarray(c1(tree, 4)["w"])
    np.testing.assert_array_equal(out3[~mask], 1.0)  # honest rows untouched
    assert not np.array_equal(out3[mask], out4[mask])  # fresh noise per round


def test_collusion_rows_agree():
    adv = AdversaryProcess(kind="collusion", f=0.4, scale=3.0, n_agents=5)
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(5, 6)), jnp.float32)}
    out = np.asarray(adv.make_corrupt()(tree, None)["w"])
    mask = adv.mask()
    byz = out[mask]
    np.testing.assert_array_equal(byz, np.broadcast_to(byz[0], byz.shape))
    np.testing.assert_array_equal(out[~mask], np.asarray(tree["w"])[~mask])
    # the common value sits `scale` away from the fleet mean
    drift = byz[0] - np.asarray(tree["w"]).mean(axis=0)
    np.testing.assert_allclose(np.linalg.norm(drift), 3.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# The MixingOps wrapper
# ---------------------------------------------------------------------------


def test_clean_path_returns_base_object():
    for base in (
        reference_mixing(make_topology("ring", 6)),
        sparse_mixing(make_sparse_topology("ring", 6)),
    ):
        assert make_adversarial_mixing(base, None, "mean", n_agents=6) is base


def test_wrapper_preserves_accounting_metadata():
    base = sparse_mixing(make_sparse_topology("ring", 6))
    wrapped = make_adversarial_mixing(
        base, "signflip:f=0.2", "trimmed", n_agents=6
    )
    assert wrapped.gossip_edges == base.gossip_edges
    assert wrapped.gossip_messages == base.gossip_messages
    assert "adv:signflip" in wrapped.name and "robust:trimmed" in wrapped.name


def test_adversarial_network_unwraps_to_base():
    base = sparse_mixing(make_sparse_topology("ring", 6))
    wrapped = make_adversarial_mixing(base, "random:f=0.2", n_agents=6)
    assert isinstance(wrapped.network, AdversarialNetwork)
    assert unwrap_network(wrapped.network) is base.network
    assert unwrap_network(base.network) is base.network  # idempotent on bases


def test_wrapped_global_avg_applies_rule_to_corrupted_payloads():
    # end-to-end wiring pin: 2 flippers among 6 agents at value 1.0 — the
    # plain-mean wrapper sees {1,1,1,1,-1,-1} -> 1/3; trimmed recovers 1.0
    n = 6
    base = sparse_mixing(make_sparse_topology("full", n))
    tree = {"w": jnp.ones((n, 2), jnp.float32)}
    m_mean = make_adversarial_mixing(base, "signflip:f=0.2", "mean", n_agents=n)
    m_trim = make_adversarial_mixing(base, "signflip:f=0.2", "trimmed:f=0.2",
                                     n_agents=n)
    np.testing.assert_allclose(
        np.asarray(m_mean.global_avg(tree)["w"]), 1.0 / 3.0, rtol=1e-6
    )
    np.testing.assert_allclose(np.asarray(m_trim.global_avg(tree)["w"]), 1.0)


# ---------------------------------------------------------------------------
# Lemma 1: where gradient tracking's invariant survives and where it breaks
# ---------------------------------------------------------------------------


def _tracking_deviation(mixing, n=8, seed=0, rounds=3):
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n, seed=seed)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, eta_c=0.9, p=0.5)
    sampler = sampler_factory(2, seed=seed)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    state = init_state(loss_fn, x0, sampler(-1)[1])
    fn = jax.jit(make_round_fn(loss_fn, cfg, mixing, global_round=True))
    for k in range(rounds):
        state, _ = fn(state, *sampler(k))
    mean0 = lambda t: jax.tree.map(lambda v: jnp.mean(v, axis=0), t)
    return _max_abs_diff(mean0(state.y), mean0(state.g))


def test_lemma1_survives_clean_breaks_under_corruption_and_robust_rules():
    base = sparse_mixing(make_sparse_topology("ring", 8))
    clean = _tracking_deviation(base)
    corrupted = _tracking_deviation(
        make_adversarial_mixing(base, "signflip:f=0.25", "mean", n_agents=8)
    )
    robust = _tracking_deviation(
        make_adversarial_mixing(base, None, "trimmed:f=0.2", n_agents=8)
    )
    # clean mean aggregation preserves mean(Y) == mean(G) exactly (Lemma 1);
    # flipped payloads break it outright, and even a *clean* fleet under a
    # non-mean rule loses the exact invariant (trimming is not the mean) —
    # the documented trade for bounded aggregate error under attack.
    assert clean < 1e-5
    assert corrupted > 1e-3
    assert robust > 10 * max(clean, 1e-7)


# ---------------------------------------------------------------------------
# ExperimentSpec wiring: validation, JSON, accounting, History series
# ---------------------------------------------------------------------------


def _data(n=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(240, 5))
    y = np.sign(rng.normal(size=240))
    return FederatedDataset.from_arrays(x, y, n, heterogeneous=False, seed=seed)


def _experiment(n=6, rounds=6, **spec_kw):
    from repro.models import simple as S

    data = _data(n)
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=n, t_o=2, eta_l=0.1, p=0.5, seed=0,
        rounds=rounds, eval_every=max(1, rounds // 2), **spec_kw
    )
    return Experiment(
        spec,
        loss_fn=S.logreg_loss,
        params0={"w": jnp.zeros((5,), jnp.float32)},
        sampler_factory=lambda s: RoundSampler(
            data, batch_size=8, t_o=s.config.t_o, seed=s.config.seed
        ),
        eval_fn=lambda params: {
            "loss": float(S.logreg_loss(
                params, (jnp.asarray(data.x_test), jnp.asarray(data.y_test))
            ))
        },
    )


def test_spec_validates_adversary_and_robust():
    _experiment(adversary="signflip:f=0.2", robust_agg="trimmed")  # fine
    with pytest.raises(ValueError):
        ExperimentSpec.create(algo="pisco", n_agents=6, adversary="bogus:f=0.2")
    with pytest.raises(ValueError):
        ExperimentSpec.create(algo="pisco", n_agents=6, robust_agg="huber")
    with pytest.raises(ValueError):  # robust rules need everyone's upload
        ExperimentSpec.create(
            algo="pisco", n_agents=6, robust_agg="median", participation=0.5
        )
    with pytest.raises(ValueError):  # ... and a synchronous server round
        ExperimentSpec.create(
            algo="pisco", n_agents=6, robust_agg="median",
            driver="events", systems="uniform", async_="constant:buffer=3",
        )


def test_spec_json_round_trip_and_legacy_payloads():
    spec = ExperimentSpec.create(
        algo="pisco", n_agents=8, adversary="signflip:f=0.25",
        robust_agg="trimmed:f=0.25", rounds=4,
    )
    again = ExperimentSpec.from_json(spec.to_json())
    assert again.adversary == "signflip:f=0.25"
    assert again.robust_agg == "trimmed:f=0.25"
    assert again == spec
    # payloads written before this subsystem existed load as clean specs
    legacy = spec.to_dict()
    del legacy["adversary"], legacy["robust_agg"]
    old = ExperimentSpec.from_dict(legacy)
    assert old.adversary is None and old.robust_agg == "mean"


@pytest.mark.slow
def test_accounting_identical_clean_vs_adversarial():
    # Byzantine agents send *wrong* bytes, not fewer: pricing cannot tell
    h_clean = _experiment().run()
    h_adv = _experiment(adversary="signflip:f=0.2", robust_agg="trimmed").run()
    assert h_adv.accountant.total_bytes == h_clean.accountant.total_bytes
    assert h_adv.to_dict()["accountant"] == h_clean.to_dict()["accountant"]


def test_history_records_mask_and_per_agent_eval():
    h = _experiment(adversary="signflip:f=0.2").run()
    mask = h.adversary_mask
    assert isinstance(mask, list) and len(mask) == 6 and sum(mask) == 2
    assert h.eval_per_agent and all(
        "honest_loss" in e and "byz_loss" in e and isinstance(e["round"], int)
        for e in h.eval_per_agent
    )
    d = json.loads(json.dumps(h.to_dict()))
    assert d["adversary_mask"] == mask
    assert len(d["eval_per_agent"]) == len(h.eval_per_agent)
    # clean runs record no mask and no per-agent series
    h0 = _experiment().run()
    assert h0.adversary_mask is None and h0.eval_per_agent == []
    assert json.loads(json.dumps(h0.to_dict()))["adversary_mask"] is None


# ---------------------------------------------------------------------------
# Driver conformance: loop == scan under every kind; events trivial path runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,network", [
    ("signflip:f=0.2", None),
    # the remaining kinds are full-lane only — each case is two jitted runs,
    # and the fast lane's 5-minute wall already carries signflip parity plus
    # the wrapper unit pins above
    pytest.param("random:f=0.2,scale=0.5", None,
                 marks=pytest.mark.slow),    # exercises the round operand
    pytest.param("random:f=0.2,scale=0.5", "bernoulli:0.3",
                 marks=pytest.mark.slow),    # ... composed with a base slot
    pytest.param("collusion:f=0.2,scale=0.5", None,
                 marks=pytest.mark.slow),
])
def test_loop_and_scan_drivers_agree_under_adversary(kind, network):
    h_loop = _experiment(
        adversary=kind, robust_agg="trimmed", driver="loop", network=network
    ).run()
    h_scan = _experiment(
        adversary=kind, robust_agg="trimmed", driver="scan", network=network
    ).run()
    np.testing.assert_allclose(h_loop.loss, h_scan.loss, rtol=1e-5, atol=1e-6)
    assert h_loop.is_global == h_scan.is_global


@pytest.mark.slow
def test_events_trivial_path_matches_scan_under_adversary():
    from repro.sim import FREE_NETWORK

    kw = dict(adversary="random:f=0.2,scale=0.5", robust_agg="trimmed",
              systems=FREE_NETWORK)
    h_scan = _experiment(driver="scan", **kw).run()
    h_ev = _experiment(driver="events", **kw).run()
    np.testing.assert_array_equal(h_scan.loss, h_ev.loss)
    assert h_ev.adversary_mask == h_scan.adversary_mask
