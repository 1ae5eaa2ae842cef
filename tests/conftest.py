"""Shared fixtures. NOTE: no XLA_FLAGS here — unit/smoke tests must see the
single real CPU device (the 512-device override belongs to dryrun.py only)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


def make_logreg_problem(n_agents=8, d=16, m=64, seed=0, heterogeneous=True):
    """Tiny logistic-regression federated problem used across tests."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=d)
    x = rng.normal(size=(n_agents * m, d))
    logits = x @ w_true
    y = np.where(logits + 0.2 * rng.normal(size=len(x)) > 0, 1.0, -1.0)
    if heterogeneous:
        order = np.argsort(y, kind="stable")
        x, y = x[order], y[order]
    x = x.reshape(n_agents, m, d)
    y = y.reshape(n_agents, m)

    xd, yd = jnp.asarray(x), jnp.asarray(y)

    def loss_fn(params, batch):
        a, lab = batch
        lg = a @ params["w"]
        return jnp.mean(jnp.log1p(jnp.exp(-lab * lg)))

    def full_grad_sq(params):
        def floss(p):
            lg = jnp.einsum("amd,d->am", xd, p["w"])
            return jnp.mean(jnp.log1p(jnp.exp(-yd * lg)))

        g = jax.grad(floss)(params)
        return float(sum(jnp.sum(v**2) for v in jax.tree.leaves(g)))

    def sampler_factory(t_o, b=16, seed=1):
        srng = np.random.default_rng(seed)

        def sampler(k):
            idx = srng.integers(0, m, size=(t_o + 1, n_agents, b))
            xb = jnp.asarray(
                np.take_along_axis(x[None], idx[..., None], axis=2)
            )
            yb = jnp.asarray(np.take_along_axis(y[None], idx, axis=2))
            return (xb[:t_o], yb[:t_o]), (xb[-1], yb[-1])

        return sampler

    return loss_fn, full_grad_sq, sampler_factory, d


# ---------------------------------------------------------------------------
# Reference mixers: dense W @ x, the yardstick for the edge-list mixers
# ---------------------------------------------------------------------------


def reference_mix(tree, w):
    """``W @ x`` along the leading agent axis of every leaf, in float32 at
    HIGHEST precision (a TPU's default precision would round to bfloat16)."""
    import jax.numpy as jnp

    w = jnp.asarray(w, jnp.float32)

    def mix(x):
        out = jnp.tensordot(w, x.astype(jnp.float32), axes=1,
                            precision=jax.lax.Precision.HIGHEST)
        return out.astype(x.dtype)

    return jax.tree.map(mix, tree)


def reference_mixing(topo):
    """Static mixers over the dense W of a ``Topology`` (``.w``) or a
    ``SparseTopology`` (``.dense_w()``), priced over the same edges."""
    from repro.core import MixingOps, topology_edges
    from repro.utils.pytree import tree_agent_mean

    w = topo.dense_w() if hasattr(topo, "dense_w") else topo.w
    return MixingOps(
        gossip=lambda tree: reference_mix(tree, w),
        global_avg=tree_agent_mean,
        name=f"reference/{topo.name}",
        gossip_edges=len(topology_edges(topo)),
    )


class ReferenceNetwork:
    """The drivers' network contract over dense per-round W_k, realized by
    the process's host-side ``realize`` (full participation)."""

    def __init__(self, process):
        from repro.core.mixing import DynamicWSlot

        self.process = process
        self.participation = None
        self.slot = DynamicWSlot()

    def draw_block(self, start, stop):
        realized = [self.process.realize(k) for k in range(start, stop)]
        ws = np.stack([w for w, _ in realized]).astype(np.float32)
        messages = np.array([m for _, m in realized])
        w_server = np.zeros((stop - start, 1), np.float32)  # never read
        return ws, w_server, messages, np.full(stop - start, self.process.n_agents)

    def draw_round(self, k):
        ws, w_server, messages, parts = self.draw_block(k, k + 1)
        return ws[0], w_server[0], int(messages[0]), int(parts[0])


def reference_network_mixing(spec):
    """Dense reference mixers for an ``ExperimentSpec``'s (optionally
    dynamic) network: the frozen W, or W_k drawn per round."""
    from repro.core import MixingOps, make_topology, make_topology_process
    from repro.utils.pytree import tree_agent_mean

    assert spec.participation == 1.0
    topo = make_topology(spec.topology, spec.config.n_agents, **dict(spec.topology_kwargs))
    if spec.effective_network is None:
        return reference_mixing(topo)
    net = ReferenceNetwork(
        make_topology_process(spec.effective_network, topo, seed=spec.config.seed)
    )
    return MixingOps(
        gossip=lambda tree: reference_mix(tree, net.slot.gossip_w),
        global_avg=tree_agent_mean,
        name=f"reference-dynamic/{topo.name}",
        gossip_edges=int(topo.adj.sum()) // 2,
        network=net,
    )
