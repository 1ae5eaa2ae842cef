"""Pytree arithmetic helpers used throughout the PISCO core.

All PISCO state (model estimates ``X``, tracking variables ``Y``, last local
gradients ``G``) lives in *agent-stacked pytrees*: every leaf carries a leading
axis of size ``n_agents``.  These helpers implement the (small) linear algebra
Algorithm 1 needs on such trees without materializing flattened vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_stack(trees):
    """Stack a list of identically-structured pytrees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree, n):
    """Inverse of :func:`tree_stack`: split leading axis into ``n`` trees."""
    return [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(n)]


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


def tree_axpy(alpha, x, y):
    """alpha * x + y, leafwise."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_dot(a, b):
    """Sum of elementwise products across all leaves (a scalar)."""
    leaves = jax.tree.map(lambda x, y: jnp.sum(x * y), a, b)
    return jax.tree.reduce(jnp.add, leaves)


def tree_sq_norm(a):
    return tree_dot(a, a)


def tree_agent_mean(tree):
    """Mean over the leading (agent) axis, broadcast back to the same shape.

    This is exactly the ``X J`` operation of the paper (J = (1/n) 11^T).
    """
    return jax.tree.map(
        lambda x: jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape), tree
    )


def tree_agent_mix_sparse(tree, senders, receivers, edge_w, self_w, n_agents):
    """Sparse gossip over directed edges: ``W @ x`` without ever
    materializing W.

    Per leaf ``x`` of shape (n, ...):

        out_i = self_w[i] * x_i + sum_{e : senders[e] -> i} edge_w[e] * x_{senders[e]}

    via a gather + ``jax.ops.segment_sum`` scatter-accumulate.  For a
    symmetric realization the directed arrays are the two orientations of
    each undirected edge with the weight duplicated; per-round edge dropout
    is expressed as zeros in ``edge_w`` (fixed shapes, so scan can thread
    the weights as operands).  Accumulates in float32.
    ``core.mixing.sparse_gossip`` runs this form only for graphs that are
    not banded; banded ones take :func:`tree_agent_mix_shifts`.
    """

    def mix(x):
        xf = x.astype(jnp.float32)
        extra = (1,) * (x.ndim - 1)
        contrib = edge_w.reshape(edge_w.shape + extra) * xf[senders]
        acc = jax.ops.segment_sum(contrib, receivers, num_segments=n_agents)
        return (self_w.reshape(self_w.shape + extra) * xf + acc).astype(x.dtype)

    return jax.tree.map(mix, tree)


def tree_agent_mix_shifts(tree, offsets, weights, self_w):
    """Sparse gossip over a banded graph as weighted shifts of the agent axis.

    Per leaf ``x`` of shape (n, ...), with ``weights`` of shape (K, n):

        out_i = self_w[i] * x_i + sum_k weights[k, i] * x_{(i - offsets[k]) mod n}

    The offsets are static.  Each leaf is extended once by its wrap-around
    rows (``xp[j] = x[(j - lo) mod n]``, lo and hi rows at the two ends, as
    many as the largest shift each way), and each shift is a static slice of
    the extension, so the leaf mixes in one elementwise pass: no gather and
    no scatter.  (A ``roll`` per shift would have the TPU compiler write
    each shifted copy out first.)  Absent edges are zeros in ``weights``.
    Accumulates in float32, like the edge form.
    """
    n = self_w.shape[0]
    signed = [d - n if 2 * d > n else d for d in offsets]
    lo, hi = max([0, *signed]), max([0, *(-d for d in signed)])

    def mix(x):
        xf = x.astype(jnp.float32)
        extra = (1,) * (x.ndim - 1)
        xp = jnp.concatenate([xf[n - lo:], xf, xf[:hi]], axis=0)
        acc = self_w.reshape(self_w.shape + extra) * xf
        for k, d in enumerate(signed):
            acc = acc + weights[k].reshape((n,) + extra) * xp[lo - d:lo - d + n]
        return acc.astype(x.dtype)

    return jax.tree.map(mix, tree)


def tree_agent_masked_mean(tree, mask):
    """Sampled-to-sampled server round in O(n): participants (``mask`` 1.0)
    average among themselves, absentees hold.  Equals applying the dense
    doubly stochastic S_k of ``ParticipationProcess.server_matrix_at``."""

    def leaf(x):
        xf = x.astype(jnp.float32)
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
        total = jnp.sum(m * xf, axis=0, keepdims=True)
        count = jnp.maximum(jnp.sum(mask), 1.0)
        avg = total / count
        return (m * avg + (1.0 - m) * xf).astype(x.dtype)

    return jax.tree.map(leaf, tree)


def tree_agent_weighted_mean(tree, w, keep):
    """Staleness-weighted server round in O(n): ``out_i = keep_i * x_i +
    (1 - keep_i) * sum_j w_j x_j``.

    ``w`` is an (n,) weight vector summing to one over the participating
    agents (zeros elsewhere) — the buffered-async aggregator's staleness
    weights; ``keep`` is 1.0 for agents holding their iterate (absentees).
    With uniform weights over the participants this equals
    :func:`tree_agent_masked_mean`; with ``keep = 0`` and ``w = 1/n`` it is
    the plain global average up to float reassociation."""

    def leaf(x):
        xf = x.astype(jnp.float32)
        wv = w.reshape(w.shape + (1,) * (x.ndim - 1))
        kv = keep.reshape(keep.shape + (1,) * (x.ndim - 1))
        avg = jnp.sum(wv * xf, axis=0, keepdims=True)
        return (kv * xf + (1.0 - kv) * avg).astype(x.dtype)

    return jax.tree.map(leaf, tree)


def tree_agent_trimmed_mean(tree, trim: int):
    """Coordinate-wise trimmed mean over the agent axis, broadcast back.

    Per leaf and per coordinate the ``trim`` smallest and ``trim`` largest
    agent values are discarded and the rest averaged — the classic
    Byzantine-robust server rule: up to ``trim`` arbitrary outliers per side
    cannot move the aggregate outside the honest value range.  ``trim = 0``
    equals :func:`tree_agent_mean` exactly.  Callers must guarantee
    ``n_agents - 2 * trim >= 1``.
    """
    trim = int(trim)

    def leaf(x):
        n = x.shape[0]
        s = jnp.sort(x.astype(jnp.float32), axis=0)
        kept = s[trim : n - trim] if trim > 0 else s
        m = jnp.mean(kept, axis=0, keepdims=True)
        return jnp.broadcast_to(m, x.shape).astype(x.dtype)

    return jax.tree.map(leaf, tree)


def tree_agent_median(tree):
    """Coordinate-wise median over the agent axis, broadcast back — robust to
    strictly fewer than half the agents being corrupted per coordinate."""

    def leaf(x):
        m = jnp.median(x.astype(jnp.float32), axis=0, keepdims=True)
        return jnp.broadcast_to(m, x.shape).astype(x.dtype)

    return jax.tree.map(leaf, tree)


def tree_agent_krum(tree, n_byz: int):
    """Krum-style selection over the agent axis, broadcast back.

    Scores each agent by the summed squared distance (across *all* leaves) to
    its ``n - n_byz - 2`` closest peers and broadcasts the minimizer's whole
    pytree — the aggregate is always one agent's actual submission, never a
    blend containing corrupted coordinates.  For tiny fleets the neighbor
    count is floored at one.
    """
    leaves = jax.tree.leaves(tree)
    n = leaves[0].shape[0]
    d2 = jnp.zeros((n, n), dtype=jnp.float32)
    for x in leaves:
        xf = x.reshape(n, -1).astype(jnp.float32)
        sq = jnp.sum(xf * xf, axis=1)
        d2 = d2 + jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * xf @ xf.T, 0.0)
    m = max(1, n - int(n_byz) - 2)
    # exclude self-distance (zero) from every agent's closest-neighbor set
    d2 = d2 + jnp.where(jnp.eye(n, dtype=bool), jnp.inf, 0.0)
    scores = jnp.sum(jnp.sort(d2, axis=1)[:, :m], axis=1)
    sel = jnp.argmin(scores)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[sel][None], x.shape).astype(x.dtype), tree
    )


def tree_size(tree) -> int:
    """Total number of scalar elements."""
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(tree))
