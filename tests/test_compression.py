"""Compressed-gossip subsystem tests.

* Round-trip error bounds per compressor (quantizer scale bound, top-k
  contraction), and unbiasedness of stochastic rounding.
* Error-feedback residual contraction (the δ-property EF convergence needs).
* Mean preservation of compressed gossip — Lemma 1's invariant
  (mean_i y_i == mean_i g_i) must survive compression of Y.
* Pallas kernel vs kernels/ref.py parity on odd / non-multiple-of-128 shapes.
* CommAccountant byte totals vs the closed-form RoundByteModel for Bernoulli
  and periodic schedules.
* End-to-end: compressed PISCO matches the uncompressed final gradient norm
  within 2x rounds at >= 4x fewer gossip bytes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import make_logreg_problem
from repro.core import (
    CommAccountant,
    PiscoConfig,
    StochasticQuantizer,
    TopKCompressor,
    IdentityCompressor,
    compress_mixing,
    init_state,
    init_compression_state,
    make_byte_model,
    make_compressor,
    make_round_fn,
    make_sparse_topology,
    make_topology,
    message_bytes,
    replicate_params,
    run_training,
    sparse_mixing,
)
from repro.kernels import quantize as Q
from repro.kernels import ref as R


def _tree_mean0(tree):
    return jax.tree.map(lambda v: jnp.mean(v, axis=0), tree)


def _max_abs_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# compressor round-trip bounds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [4, 8])
def test_quantizer_roundtrip_error_bound(bits):
    """Deterministic rounding: per-element error <= scale/2, rowwise scale."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 97), jnp.float32)
    q = StochasticQuantizer(bits=bits, stochastic=False).compress(x)
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax
    assert float(jnp.max(jnp.abs(q - x) - 0.5 * scale)) <= 1e-6
    assert q.dtype == x.dtype and q.shape == x.shape


def test_stochastic_quantizer_is_unbiased():
    """E[q(x)] == x over keys (floor + uniform carry rounds unbiasedly)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.float32)
    comp = StochasticQuantizer(bits=4, stochastic=True)
    reps = jnp.stack(
        [comp.compress(x, jax.random.PRNGKey(k)) for k in range(400)]
    )
    bias = float(jnp.max(jnp.abs(jnp.mean(reps, 0) - x)))
    scale = float(jnp.max(jnp.abs(x))) / 7.0
    # CLT: bias ~ scale / sqrt(400) ~ 0.05 * scale; allow 4 sigma
    assert bias < 0.2 * scale


@given(frac=st.floats(0.05, 0.9), seed=st.integers(0, 30))
@settings(max_examples=10, deadline=None)
def test_topk_contraction_property(frac, seed):
    """||x - topk(x)||^2 <= (1 - k/d) ||x||^2 per agent row."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (5, 40), jnp.float32)
    comp = TopKCompressor(fraction=frac)
    q = comp.compress(x)
    k = comp.k_for(40)
    err = jnp.sum((x - q) ** 2, axis=1)
    full = jnp.sum(x**2, axis=1)
    assert float(jnp.max(err - (1.0 - k / 40.0) * full)) <= 1e-5
    # exactly k survivors per row
    assert int(jnp.max(jnp.sum(q != 0, axis=1))) <= k


def test_error_feedback_residual_contracts():
    """The EF residual stays bounded (contraction): after many compressed
    gossip steps, ||residual|| never blows past the offered signal."""
    n, d = 8, 32
    base = sparse_mixing(make_sparse_topology("ring", n))
    mix = compress_mixing(base, TopKCompressor(0.25), error_feedback=True)
    cg = mix.compression
    tree = {"w": jax.random.normal(jax.random.PRNGKey(2), (n, d), jnp.float32)}
    res = jax.tree.map(jnp.zeros_like, tree)
    key = jax.random.PRNGKey(0)
    for i in range(30):
        key, k = jax.random.split(key)
        out, res = cg(tree, res, k)
        m_norm = float(jnp.sqrt(jnp.sum((tree["w"] + 0) ** 2)))
        r_norm = float(jnp.sqrt(jnp.sum(res["w"] ** 2)))
        # delta-contraction: residual < (1-k/d)^(1/2) * ||message|| and the
        # geometric series it induces stays below ~ (1/delta) * signal
        assert r_norm <= 4.0 * m_norm
        tree = out
    assert np.isfinite(r_norm)


# ---------------------------------------------------------------------------
# mean preservation (Lemma 1 under compression)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", ["q8", "q4", "top0.2"])
@pytest.mark.parametrize("ef", [True, False])
def test_compressed_gossip_preserves_agent_mean(spec, ef):
    n, d = 8, 33
    base = sparse_mixing(make_sparse_topology("ring", n))
    mix = compress_mixing(base, make_compressor(spec), error_feedback=ef)
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (n, d), jnp.float32)}
    out = mix.gossip(tree)  # stateless path
    assert _max_abs_diff(_tree_mean0(out), _tree_mean0(tree)) < 1e-6
    if ef:
        cg = mix.compression
        res = jax.tree.map(jnp.zeros_like, tree)
        out2, _ = cg(tree, res, jax.random.PRNGKey(0))
        assert _max_abs_diff(_tree_mean0(out2), _tree_mean0(tree)) < 1e-6


@given(
    spec=st.sampled_from(["q8", "q4", "top0.25"]),
    t_o=st.integers(1, 3),
    seed=st.integers(0, 10),
)
@settings(max_examples=6, deadline=None)
def test_lemma1_survives_compression(spec, t_o, seed):
    """mean(Y) == mean(G) after compressed gossip rounds (EF path)."""
    n = 8
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n, seed=seed)
    cfg = PiscoConfig(n_agents=n, t_o=t_o, eta_l=0.1, eta_c=0.9, p=0.5)
    base = sparse_mixing(make_sparse_topology("ring", n))
    mix = compress_mixing(base, make_compressor(spec), error_feedback=True)
    sampler = sampler_factory(t_o, seed=seed)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    state = init_compression_state(
        init_state(loss_fn, x0, sampler(-1)[1]), mix
    )
    fn = jax.jit(make_round_fn(loss_fn, cfg, mix, global_round=False))
    for k in range(3):
        state, _ = fn(state, *sampler(k))
    assert _max_abs_diff(_tree_mean0(state.y), _tree_mean0(state.g)) < 1e-5


# ---------------------------------------------------------------------------
# Pallas kernels vs references (odd / tail shapes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(5, 37), (8, 200), (3, 130), (7, 1000), (1, 1)])
@pytest.mark.parametrize("bits", [4, 8])
def test_quant_dequant_kernel_matches_ref(shape, bits):
    x = jax.random.normal(jax.random.PRNGKey(sum(shape)), shape, jnp.float32)
    out = Q.rowwise_quant_dequant(x, bits=bits, interpret=True)
    ref = R.rowwise_quant_dequant_ref(x, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # and the kernel agrees with the jnp compressor's deterministic path
    comp = StochasticQuantizer(bits=bits, stochastic=False).compress(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(comp), atol=1e-6)


@pytest.mark.parametrize("shape", [(5, 37), (8, 200), (6, 643)])
@pytest.mark.parametrize("bits", [4, 8])
def test_fused_compressed_mix_kernel_matches_ref(shape, bits):
    n, d = shape
    x = jax.random.normal(jax.random.PRNGKey(d), shape, jnp.float32)
    w = jnp.asarray(make_topology("ring", n).w, jnp.float32)
    out = Q.fused_compressed_mix(x, w, bits=bits, interpret=True)
    ref = R.compressed_mix_ref(x, w, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # the fused form is mean-preserving too
    np.testing.assert_allclose(
        np.asarray(jnp.mean(out, 0)), np.asarray(jnp.mean(x, 0)), atol=1e-6
    )


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------


def test_message_bytes_closed_form():
    n, d = 8, 100
    template = {"w": jnp.zeros((n, d)), "b": jnp.zeros((n,))}
    # fp32: (100 + 1) * 4 bytes
    assert message_bytes(None, template, n) == 101 * 4
    # int8: ceil((100*8 + 32 + 1*8 + 32) / 8)
    assert message_bytes(StochasticQuantizer(bits=8), template, n) == -(
        -(100 * 8 + 32 + 8 + 32) // 8
    )
    # top-k keeps ceil(0.1*100)=10 and ceil(0.1*1)=1 pairs of (fp32, int32)
    assert message_bytes(TopKCompressor(0.1), template, n) == 11 * 8


@pytest.mark.parametrize("p", [0.0, 0.35, 1.0])
def test_accountant_bytes_match_model_bernoulli(p):
    """Realized byte totals == closed form from the realized round counts."""
    n = 8
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=1, eta_l=0.1, eta_c=1.0, p=p, seed=4)
    base = sparse_mixing(make_sparse_topology("ring", n))
    mix = compress_mixing(base, StochasticQuantizer(bits=8))
    hist = run_training(
        "pisco", loss_fn, replicate_params({"w": jnp.zeros(d)}, n), cfg, mix,
        sampler_factory(1), rounds=20,
    )
    acct = hist.accountant
    bm = hist.byte_model
    assert acct.total == 20
    assert acct.agent_to_agent_bytes == acct.agent_to_agent * bm.gossip_round_bytes
    assert acct.agent_to_server_bytes == acct.agent_to_server * bm.server_round_bytes
    assert acct.total_bytes == bm.total_bytes(acct.agent_to_agent, acct.agent_to_server)
    # closed-form sizing: ring of 8 has 8 undirected edges => 16 directed
    # messages per mix, 2 mixes/round (X and Y); server = 2 dirs * 8 agents
    gossip_msg = -(-(d * 8 + 32) // 8)  # int8 payload + fp32 scale
    server_msg = d * 4
    assert bm.gossip_round_bytes == 2 * 16 * gossip_msg
    assert bm.server_round_bytes == 2 * 2 * n * server_msg
    if p == 0.0:
        assert acct.agent_to_server == 0
        assert acct.total_bytes == bm.expected_bytes(20, 0.0)
    if p == 1.0:
        assert acct.agent_to_agent == 0
        assert acct.total_bytes == bm.expected_bytes(20, 1.0)


def test_accountant_bytes_match_model_periodic():
    """gossip_pga uses the every-H schedule: exact closed form in rounds."""
    n = 6
    loss_fn, _, sampler_factory, d = make_logreg_problem(n_agents=n)
    # gossip_pga derives H = round(1/p); p=0.25 -> server every 4th round
    cfg = PiscoConfig(n_agents=n, t_o=1, eta_l=0.1, eta_c=1.0, p=0.25, seed=0)
    base = sparse_mixing(make_sparse_topology("ring", n))
    mix = compress_mixing(base, StochasticQuantizer(bits=8))
    rounds = 21
    hist = run_training(
        "gossip_pga", loss_fn, replicate_params({"w": jnp.zeros(d)}, n), cfg,
        mix, sampler_factory(1), rounds=rounds,
    )
    acct = hist.accountant
    bm = hist.byte_model
    assert acct.agent_to_server == rounds // 4
    assert acct.total_bytes == bm.periodic_bytes(rounds, 4)
    assert acct.total_bytes == bm.total_bytes(acct.agent_to_agent, acct.agent_to_server)


def test_record_backward_compatible():
    acct = CommAccountant()
    acct.record(False)  # no byte argument — pre-compression call sites
    acct.record(True, 100)
    assert acct.total == 2 and acct.total_bytes == 100


# ---------------------------------------------------------------------------
# end-to-end acceptance: same accuracy, >= 4x fewer gossip bytes
# ---------------------------------------------------------------------------


def test_compressed_pisco_matches_uncompressed_at_4x_fewer_bytes():
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    base = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.15, eta_c=1.0, p=0.1, seed=0)

    def drive(mix, rounds):
        return run_training(
            "pisco", loss_fn, x0, cfg, mix, sampler_factory(2), rounds=rounds,
            eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)}, eval_every=1,
        )

    rounds = 60
    hist_fp = drive(base, rounds)
    target = hist_fp.eval_metrics[-1]["grad_sq"]

    mix_c = compress_mixing(base, StochasticQuantizer(bits=4), error_feedback=True)
    hist_c = drive(mix_c, 2 * rounds)
    # first instantaneous crossing of the fp32 run's final quality
    vals_c = np.array([m["grad_sq"] for m in hist_c.eval_metrics])
    hits = np.nonzero(vals_c <= target)[0]
    assert hits.size, "compressed run never matched uncompressed quality"
    assert hits[0] + 1 <= 2 * rounds  # within 2x the uncompressed budget
    # >= 4x fewer bytes per gossip round (int4 + rowwise scale overhead)
    assert hist_fp.byte_model.gossip_round_bytes >= 4 * hist_c.byte_model.gossip_round_bytes
    # identical server pricing (full precision both)
    assert hist_fp.byte_model.server_round_bytes == hist_c.byte_model.server_round_bytes


def test_gamma_auto_selection():
    """Contractive top-k gets the damped CHOCO step; quantizers run
    undamped; explicit gamma wins.  (Undamped top-k diverges under large
    local steps — see DESIGN.md §7.)"""
    base = sparse_mixing(make_sparse_topology("ring", 6))
    assert compress_mixing(base, TopKCompressor(0.1)).compression.gamma == 0.5
    assert compress_mixing(base, StochasticQuantizer(8)).compression.gamma == 1.0
    mix = compress_mixing(base, TopKCompressor(0.1), gamma=0.3)
    assert mix.compression.gamma == 0.3
    # the damped form still preserves the agent mean exactly
    tree = {"w": jax.random.normal(jax.random.PRNGKey(5), (6, 21), jnp.float32)}
    out = mix.gossip(tree)
    assert _max_abs_diff(_tree_mean0(out), _tree_mean0(tree)) < 1e-6


def test_disabled_compression_is_plain_mixing():
    """compress_mixing(identity) must return the base ops untouched, so the
    uncompressed path is bit-identical to the pre-compression code."""
    base = sparse_mixing(make_sparse_topology("ring", 4))
    assert compress_mixing(base, IdentityCompressor()) is base
    assert base.compression is None
