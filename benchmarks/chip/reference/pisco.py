"""Plain float32 reference of PISCO's rounds (arXiv:2311.18787, Algorithm 1).

Imports nothing of the program.  Agents are stacked on a leading axis.
Round k, with W^k the server average J when the round's flag is set and the
topology's Metropolis matrix otherwise:

    local steps t = 1..T_o:  X <- X - eta_l Y;  G' = grad(X; Z_t);  Y <- Y + G' - G;  G <- G'
    X^{k+1} = ((1 - eta_c) X^k + eta_c (X - eta_l Y)) W^k
    G' = grad(X^{k+1}; Z_comm);  Y <- (Y + G' - G) W^k;  G <- G'

starting from X^0 = x0 for every agent and Y^0 = G^0 = grad(X^0; Z^0).  A
round's loss is the mean over agents of its T_o + 1 minibatch losses.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def metropolis_ring(n: int):
    """X -> X W for the Metropolis weights of a ring of ``n`` agents."""
    if n == 1:
        return lambda t: t
    if n == 2:  # each agent's two ring neighbours are one agent: W = J
        return server_mean
    return lambda t: jax.tree.map(
        lambda a: (a + jnp.roll(a, 1, axis=0) + jnp.roll(a, -1, axis=0)) / 3.0, t)


def server_mean(t):
    return jax.tree.map(lambda a: jnp.broadcast_to(jnp.mean(a, axis=0, keepdims=True), a.shape), t)


TOPOLOGIES = {"ring": metropolis_ring}


def _slice(tree, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def make_steps(loss, *, n_agents, t_o, eta_l, eta_c, topology, agent_chunk):
    """The reference's three jitted programs: ``start(x0, batch0)`` gives
    (X^0, Y^0, G^0); ``local_step(x, y, g, batch)`` and ``comm_step(x_k, x,
    y, g, batch, server)`` each give (x, y, g, mean loss)."""
    vg = jax.vmap(jax.value_and_grad(loss))
    gossip = TOPOLOGIES[topology](n_agents)

    def grads(x, batch):
        if agent_chunk >= n_agents:
            return vg(x, batch)
        parts = [vg(_slice(x, i, i + agent_chunk), _slice(batch, i, i + agent_chunk))
                 for i in range(0, n_agents, agent_chunk)]
        return jax.tree.map(lambda *p: jnp.concatenate(p), *parts)

    def axpy(a, x, y):  # a x + y, leafwise
        return jax.tree.map(lambda u, v: a * u + v, x, y)

    @jax.jit
    def start(x0, batch):
        x = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_agents,) + a.shape), x0)
        _, g = grads(x, batch)
        return x, g, g

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def local_step(x, y, g, batch):
        x = axpy(-eta_l, y, x)
        losses, g_new = grads(x, batch)
        y = jax.tree.map(lambda a, b, c: a + b - c, y, g_new, g)
        return x, y, g_new, jnp.mean(losses)

    @partial(jax.jit, static_argnums=(5,), donate_argnums=(1, 2, 3))
    def comm_step(x_k, x, y, g, batch, server):
        mix = server_mean if server else gossip
        cand = axpy(-eta_l, y, x)
        if eta_c != 1.0:
            cand = jax.tree.map(lambda a, b: (1.0 - eta_c) * a + eta_c * b, x_k, cand)
        x = mix(cand)
        losses, g_new = grads(x, batch)
        y = mix(jax.tree.map(lambda a, b, c: a + b - c, y, g_new, g))
        return x, y, g_new, jnp.mean(losses)

    return start, local_step, comm_step


def run(loss, x0, batches, flags, *, n_agents, t_o, eta_l, eta_c, topology, agent_chunk):
    """Readings of the reference after ``len(flags)`` rounds.

    ``x0`` is one agent's parameters; ``batches(k)`` gives round ``k``'s
    T_o + 1 minibatches of every agent, leaves (T_o + 1, n, ...): the first
    T_o for the local steps, the last for the communication step.  The
    initial gradient is taken on the last minibatch of round -1.  Returns
    each round's loss and, for G after the last round and for X - X^0, the
    ``norms`` of their leaves."""
    start, local_step, comm_step = make_steps(
        loss, n_agents=n_agents, t_o=t_o, eta_l=eta_l, eta_c=eta_c,
        topology=topology, agent_chunk=agent_chunk)
    x, y, g = start(x0, _step(batches(-1), t_o))
    round_losses = []
    for k, flag in enumerate(flags):
        # X^k is kept only where the interpolation with eta_c reads it
        x_k = jax.tree.map(jnp.copy, x) if eta_c != 1.0 else None
        batch = batches(k)
        step_losses = []
        for t in range(t_o):
            x, y, g, l = local_step(x, y, g, _step(batch, t))
            step_losses.append(l)
        x, y, g, l = comm_step(x_k, x, y, g, _step(batch, t_o), bool(flag))
        step_losses.append(l)
        del batch
        round_losses.append(float(jnp.mean(jnp.stack(step_losses))))

    return {"loss": round_losses, "g": norms(g),
            "dx": norms(jax.tree.map(lambda a, b: a - b[None], x, x0))}


@jax.jit
def norms(tree):
    """Each leaf's norm, and each agent's row norm of it."""
    rows = jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)), axis=1)),
                        tree)
    return jax.tree.map(jnp.linalg.norm, tree), rows


def _step(tree, t):
    return jax.tree.map(lambda a: a[t], tree)
