"""Serving subsystem tests (DESIGN.md §15).

* Delta exactness properties: top-k set-form is bit-exact whenever the index
  set covers every differing coordinate (fraction=1 for arbitrary diffs,
  partial fractions for sparse perturbations); q8 reconstruction stays within
  the quantizer bound (scale/2 per coordinate); dense is trivially lossless
  and dtype-preserving.
* Fleet memory: the delta representation is >= 10x smaller than n dense
  copies at n=64 agents.
* Exporters: ``from_history`` on a real (tiny) federated LM run and
  ``from_checkpoint`` on the saved state both round-trip bit-exactly at
  fraction=1.
* Engine: token streams through the continuous batcher are bit-identical
  between the delta engine (both materialize modes) and the dense baseline.
* Batcher/load mechanics on a stub engine: admission/eviction lifecycle,
  finish-at-admission, EOS, hand-checked latency arithmetic under fixed
  costs, arrival-process determinism.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.models import ModelConfig, get_bundle
from repro.serve import (
    ArrivalProcess,
    ContinuousBatcher,
    DecodeEngine,
    DeltaSpec,
    FleetDelta,
    Request,
    StepCosts,
    make_requests,
    materialize,
    materialize_fleet,
    run_load,
)
from repro.serve.delta import DenseDelta, TopKDelta

TINY = ModelConfig(
    name="serve-test-tiny",
    arch_type="dense",
    n_layers=1,
    d_model=32,
    n_heads=2,
    n_kv_heads=1,
    head_dim=16,
    d_ff=64,
    vocab_size=64,
    mlp_type="swiglu",
    dtype="float32",
    attn_chunk=32,
    remat=False,
)


@pytest.fixture(scope="module")
def bundle():
    return get_bundle(TINY)


@pytest.fixture(scope="module")
def base(bundle):
    return bundle.init(jax.random.PRNGKey(7))


def _rand_tree(rng, n):
    """Agent-stacked pytree with 1-D and 2-D leaves."""
    return {
        "w": rng.normal(size=(n, 6, 5)).astype(np.float32),
        "b": rng.normal(size=(n, 7)).astype(np.float32),
    }


def _assert_bit_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype
        assert np.array_equal(x, y), "leaves differ"


# ---------------------------------------------------------------------------
# Delta exactness
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10))
@settings(max_examples=6, deadline=None)
def test_topk_full_fraction_bit_exact(seed):
    """fraction=1 covers every coordinate -> set-form is bit-exact for
    arbitrary (dense) diffs."""
    rng = np.random.default_rng(seed)
    stacked = _rand_tree(rng, 4)
    fleet = FleetDelta.from_stacked(stacked, DeltaSpec.parse("topk:f=1"))
    _assert_bit_equal(materialize(fleet.base, fleet.deltas), stacked)


@given(seed=st.integers(0, 10))
@settings(max_examples=6, deadline=None)
def test_topk_partial_fraction_bit_exact_on_sparse_diffs(seed):
    """When agents deviate on <= k coordinates, partial top-k still covers
    the full differing set and reconstruction is bit-exact."""
    rng = np.random.default_rng(seed)
    n, shape = 4, (8, 10)
    d = int(np.prod(shape))
    base = rng.normal(size=shape).astype(np.float32)
    k = max(1, int(np.ceil(0.1 * d)))
    stacked = np.broadcast_to(base, (n,) + shape).copy().reshape(n, d)
    for i in range(n):
        idx = rng.choice(d, size=k, replace=False)
        stacked[i, idx] += rng.normal(size=k).astype(np.float32)
    stacked = {"w": stacked.reshape((n,) + shape)}
    fleet = FleetDelta.from_stacked(
        stacked, DeltaSpec(kind="topk", fraction=0.1), base={"w": base}
    )
    _assert_bit_equal(materialize(fleet.base, fleet.deltas), stacked)


def test_q8_reconstruction_within_quantizer_bound():
    rng = np.random.default_rng(0)
    stacked = _rand_tree(rng, 4)
    fleet = FleetDelta.from_stacked(stacked, DeltaSpec.parse("topk:f=1,q8"))
    recon = materialize(fleet.base, fleet.deltas)
    for lk in ("w", "b"):
        err = np.abs(np.asarray(recon[lk]) - stacked[lk])
        scale = np.asarray(fleet.deltas[lk].scale)  # (n, 1)
        bound = scale.reshape(scale.shape[0], *([1] * (err.ndim - 1))) / 2
        assert np.all(err <= bound + 1e-7), f"q8 error exceeds scale/2 on {lk}"


def test_dense_delta_lossless_and_dtype_preserving():
    rng = np.random.default_rng(1)
    stacked = {
        "w": rng.normal(size=(3, 4, 4)).astype(np.float16),
        "c": rng.integers(0, 100, size=(3, 5)).astype(np.int32),
    }
    fleet = FleetDelta.from_stacked(stacked, DeltaSpec(kind="dense"))
    assert isinstance(fleet.deltas["w"], DenseDelta)
    _assert_bit_equal(materialize(fleet.base, fleet.deltas), stacked)


def test_lowrank_full_rank_recovers_residual():
    rng = np.random.default_rng(2)
    stacked = _rand_tree(rng, 3)
    fleet = FleetDelta.from_stacked(stacked, DeltaSpec.parse("lowrank:r=8"))
    # rank 8 >= min(6, 5): SVD is exact up to float error; 1-D leaves dense
    assert isinstance(fleet.deltas["b"], DenseDelta)
    recon = materialize(fleet.base, fleet.deltas)
    np.testing.assert_allclose(
        np.asarray(recon["w"]), stacked["w"], atol=1e-5
    )
    _assert_bit_equal({"b": recon["b"]}, {"b": stacked["b"]})


def test_delta_spec_parse_and_errors():
    assert DeltaSpec.parse("topk:f=0.1,q8") == DeltaSpec(
        kind="topk", fraction=0.1, quantize=True
    )
    assert DeltaSpec.parse("lowrank:r=8").rank == 8
    assert DeltaSpec.parse("dense").name == "dense"
    assert DeltaSpec.parse(DeltaSpec.parse("topk:f=0.1,q8").name).quantize
    with pytest.raises(ValueError):
        DeltaSpec.parse("svd")
    with pytest.raises(ValueError):
        DeltaSpec.parse("topk:f=0")
    with pytest.raises(ValueError):
        DeltaSpec.parse("topk:rank=2")
    with pytest.raises(ValueError):
        DeltaSpec(kind="dense", quantize=True)


def test_fleet_memory_ratio_at_64_agents(base):
    fleet = FleetDelta.synthetic(base, 64, seed=3)
    assert fleet.n_agents == 64
    ratio = fleet.naive_nbytes() / fleet.nbytes()
    assert ratio >= 10.0, f"expected >=10x memory saving at n=64, got {ratio:.1f}x"


def test_synthetic_fleet_is_lossless_topk(base):
    fleet = FleetDelta.synthetic(base, 5, seed=4)
    dense = materialize_fleet(fleet)
    refleet = FleetDelta.from_stacked(
        dense.stacked, DeltaSpec(kind="topk", fraction=1.0)
    )
    _assert_bit_equal(
        materialize(refleet.base, refleet.deltas), dense.stacked
    )
    assert all(
        isinstance(d, TopKDelta) for d in
        jax.tree.leaves(fleet.deltas, is_leaf=lambda x: isinstance(x, TopKDelta))
    )


# ---------------------------------------------------------------------------
# Exporters: trained history and checkpoint round-trips
# ---------------------------------------------------------------------------


def test_from_history_and_checkpoint_round_trip(bundle, tmp_path):
    from repro.checkpoint import save_checkpoint
    from repro.core import (
        PiscoConfig, make_sparse_topology, replicate_params, sparse_mixing,
        run_training,
    )

    n, seq = 2, 16
    rng = np.random.default_rng(0)

    def sampler(_k):
        toks = rng.integers(0, TINY.vocab_size, size=(2, n, 1, seq))
        return (
            {"tokens": jnp.asarray(toks[:1])},
            {"tokens": jnp.asarray(toks[1])},
        )

    cfg = PiscoConfig(n_agents=n, t_o=1, eta_l=0.05, eta_c=1.0, p=0.5, seed=0)
    x0 = replicate_params(bundle.init(jax.random.PRNGKey(1)), n)
    hist = run_training(
        "pisco", bundle.loss, x0, cfg, sparse_mixing(make_sparse_topology("ring", n)),
        sampler, rounds=2,
    )
    stacked = jax.tree.map(np.asarray, hist.agent_params())

    fleet = FleetDelta.from_history(hist, DeltaSpec.parse("topk:f=1"))
    assert fleet.n_agents == n
    _assert_bit_equal(materialize(fleet.base, fleet.deltas), stacked)

    path = save_checkpoint(str(tmp_path), 2, hist.final_state)
    fleet2 = FleetDelta.from_checkpoint(path, DeltaSpec.parse("topk:f=1"))
    _assert_bit_equal(materialize(fleet2.base, fleet2.deltas), stacked)


def test_export_fleet_round_trip(tmp_path):
    from repro.checkpoint import read_manifest
    from repro.serve import export_fleet

    rng = np.random.default_rng(5)
    stacked = _rand_tree(rng, 3)
    hist = type("H", (), {"agent_params": lambda self: stacked})()
    path = export_fleet(str(tmp_path), hist, step=7)
    assert read_manifest(path)["metadata"] == {"kind": "fleet"}
    fleet = FleetDelta.from_checkpoint(path, DeltaSpec.parse("topk:f=1"))
    _assert_bit_equal(materialize(fleet.base, fleet.deltas), stacked)


# ---------------------------------------------------------------------------
# Engine: delta-multiplexed decode is bit-identical to the dense baseline
# ---------------------------------------------------------------------------


def _serve_tokens(bundle, fleet, mode, requests):
    eng = DecodeEngine(bundle, fleet, n_slots=2, max_seq=40, materialize=mode)
    rep = run_load(
        ContinuousBatcher(eng), requests, costs=StepCosts(0.05, 0.01)
    )
    return {r.rid: list(r.tokens) for r in rep.requests}


def test_engine_bit_identical_to_dense_baseline(bundle, base):
    fleet = FleetDelta.synthetic(base, 6, seed=9)
    trace = lambda: make_requests(
        ArrivalProcess(rate=4.0), 5, n_agents=6, vocab_size=TINY.vocab_size,
        prompt_len=8, max_new_tokens=5, seed=11,
    )
    dense_toks = _serve_tokens(bundle, materialize_fleet(fleet), "admit", trace())
    assert sum(len(t) for t in dense_toks.values()) == 25
    assert _serve_tokens(bundle, fleet, "admit", trace()) == dense_toks
    assert _serve_tokens(bundle, fleet, "step", trace()) == dense_toks


def test_engine_rejects_bad_inputs(bundle, base):
    fleet = FleetDelta.synthetic(base, 2, seed=0)
    with pytest.raises(ValueError):
        DecodeEngine(bundle, fleet, materialize="eager")
    with pytest.raises(TypeError):
        DecodeEngine(bundle, {"not": "a fleet"})
    enc_dec = dataclasses.replace(TINY, is_enc_dec=True, n_encoder_layers=1)
    with pytest.raises(ValueError):
        DecodeEngine(get_bundle(enc_dec), fleet)


# ---------------------------------------------------------------------------
# Batcher / load mechanics (stub engine: no jit, pure state machine)
# ---------------------------------------------------------------------------


class StubEngine:
    """Deterministic logits: argmax = (agent_id + n_generated) % vocab."""

    vocab = 16

    def __init__(self, n_slots=2):
        self.n_slots = n_slots
        self._agents = np.zeros(n_slots, dtype=np.int64)
        self._counts = np.zeros(n_slots, dtype=np.int64)

    def _logits(self, slot):
        out = np.zeros(self.vocab, dtype=np.float32)
        out[(self._agents[slot] + self._counts[slot]) % self.vocab] = 1.0
        return out

    def admit(self, slot, agent_id, prompt):
        self._agents[slot] = agent_id
        self._counts[slot] = 0
        return self._logits(slot)

    def step(self, tokens):
        self._counts += 1
        return np.stack([self._logits(s) for s in range(self.n_slots)])

    def block_until_ready(self):
        pass


def _req(rid, agent, gen, arrival=0.0, eos=None):
    return Request(
        rid=rid, agent_id=agent, prompt=np.zeros(4, np.int32),
        max_new_tokens=gen, eos_id=eos, arrival_s=arrival,
    )


def test_batcher_admit_evict_lifecycle():
    b = ContinuousBatcher(StubEngine(n_slots=2))
    assert b.free_slots() == [0, 1]
    assert b.admit(_req(0, agent=3, gen=2)) is False
    assert b.admit(_req(1, agent=5, gen=3)) is False
    assert b.free_slots() == []
    with pytest.raises(RuntimeError):
        b.admit(_req(2, agent=0, gen=1))
    fin = b.step()  # req0 reaches 2 tokens -> evicted
    assert [r.rid for r in fin] == [0]
    assert b.free_slots() == [0]
    assert fin[0].tokens == [3, 4]  # agent 3: (3+0)%16, (3+1)%16
    fin = b.step()
    assert [r.rid for r in fin] == [1]
    assert b.completed[-1].tokens == [5, 6, 7]


def test_batcher_finishes_at_admission_and_on_eos():
    b = ContinuousBatcher(StubEngine(n_slots=1))
    assert b.admit(_req(0, agent=2, gen=1)) is True  # max_new_tokens == 1
    assert b.free_slots() == [0]
    # agent 4 emits 4 at admission -> immediate EOS
    assert b.admit(_req(1, agent=4, gen=8, eos=4)) is True
    # agent 3 emits 3, 4 -> EOS on the first decode step
    assert b.admit(_req(2, agent=3, gen=8, eos=4)) is False
    fin = b.step()
    assert [r.rid for r in fin] == [2]
    assert fin[0].tokens == [3, 4]


def test_run_load_latency_arithmetic_single_request():
    """latency = prefill + (gen-1) * decode, zero queue wait."""
    b = ContinuousBatcher(StubEngine(n_slots=2))
    reqs = [_req(0, agent=1, gen=4, arrival=1.0)]
    rep = run_load(b, reqs, costs=StepCosts(prefill_s=0.5, decode_s=0.125))
    (r,) = rep.requests
    assert r.queue_wait_s == 0.0
    assert r.prefill_s == 0.5
    np.testing.assert_allclose(r.decode_s, 3 * 0.125)
    np.testing.assert_allclose(r.latency_s, 0.5 + 3 * 0.125)
    np.testing.assert_allclose(rep.clock_s, 1.0 + 0.5 + 3 * 0.125)
    assert rep.total_tokens == 4


def test_run_load_queue_wait_when_slots_full():
    """Three simultaneous arrivals, one slot: each waits for the previous
    request's full service time."""
    b = ContinuousBatcher(StubEngine(n_slots=1))
    reqs = [_req(i, agent=1, gen=2, arrival=0.0) for i in range(3)]
    rep = run_load(b, reqs, costs=StepCosts(prefill_s=0.5, decode_s=0.25))
    by_rid = {r.rid: r for r in rep.requests}
    service = 0.5 + 0.25  # prefill + one decode step
    for i in range(3):
        np.testing.assert_allclose(by_rid[i].queue_wait_s, i * service)
    assert rep.makespan_s == pytest.approx(3 * service)


def test_arrival_processes_deterministic_and_well_formed():
    p = ArrivalProcess.parse("poisson:rate=2")
    a1, a2 = p.draw(50, seed=3), p.draw(50, seed=3)
    np.testing.assert_array_equal(a1, a2)
    assert not np.array_equal(a1, p.draw(50, seed=4))
    assert np.all(np.diff(a1) >= 0)

    b = ArrivalProcess.parse("bursty:rate=4,burst=5")
    times = b.draw(20, seed=0)
    assert len(np.unique(times)) == 4  # 20 arrivals in groups of 5
    with pytest.raises(ValueError):
        ArrivalProcess.parse("uniform:rate=1")
    with pytest.raises(ValueError):
        ArrivalProcess.parse("poisson:rate=0")

    reqs = make_requests(p, 10, n_agents=6, vocab_size=32, seed=2)
    reqs2 = make_requests(p, 10, n_agents=6, vocab_size=32, seed=2)
    assert [r.agent_id for r in reqs] == [r.agent_id for r in reqs2]
    assert all(0 <= r.agent_id < 6 for r in reqs)
    assert all(r.prompt.dtype == np.int32 for r in reqs)
    np.testing.assert_array_equal(reqs[0].prompt, reqs2[0].prompt)


def test_temperature_sampling_uses_domain_separated_streams():
    """Same rid+step -> same draw; different rid -> (almost surely)
    different stream. Greedy path must ignore the key entirely."""
    b = ContinuousBatcher(StubEngine(n_slots=2), temperature=1.0, seed=0)
    logits = np.linspace(0.0, 1.0, StubEngine.vocab).astype(np.float32)
    r0, r1 = _req(0, agent=1, gen=4), _req(1, agent=1, gen=4)
    draws0 = [b._sample(r0, logits) for _ in range(3)]
    assert draws0[0] == draws0[1] == draws0[2]  # pure in (rid, n_tokens)
    b2 = ContinuousBatcher(StubEngine(n_slots=2), temperature=1.0, seed=0)
    assert b2._sample(r0, logits) == draws0[0]
    greedy = ContinuousBatcher(StubEngine(n_slots=2))
    assert greedy._sample(r0, logits) == StubEngine.vocab - 1
