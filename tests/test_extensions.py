"""Beyond-paper extensions: compressed gossip, hierarchical mixing glue."""
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
from repro.core.mixing import compressed_mixing


@pytest.mark.parametrize("bits", [8, 4])
def test_compressed_gossip_quantizes(bits):
    base = sparse_mixing(make_sparse_topology("ring", 4))
    comp = compressed_mixing(base, bits=bits)
    tree = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(4, 16)), jnp.float32)}
    out_c = comp.gossip(tree)
    out_b = base.gossip(tree)
    err = float(jnp.max(jnp.abs(out_c["w"] - out_b["w"])))
    # quantization error bounded by scale ~ max|x| / qmax
    bound = float(jnp.max(jnp.abs(tree["w"]))) / (2 ** (bits - 1) - 1)
    assert 0 < err <= bound + 1e-6
    # global averaging stays exact
    np.testing.assert_allclose(
        np.asarray(comp.global_avg(tree)["w"]), np.asarray(base.global_avg(tree)["w"])
    )


def test_pisco_converges_with_int8_gossip():
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.15, eta_c=1.0, p=0.1, seed=0)
    base = sparse_mixing(make_sparse_topology("ring", n))
    comp = compressed_mixing(base, bits=8)
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    hist = run_training(
        "pisco", loss_fn, x0, cfg, comp, sampler_factory(2),
        rounds=50,
        eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)},
        eval_every=10,
    )
    assert hist.eval_metrics[-1]["grad_sq"] < 0.05
    assert hist.loss[-1] < hist.loss[0]
