"""Mixing operators: the communication layer of PISCO (paper eq. 4a/4c).

Two families, one interface (:class:`MixingOps`):

* **Network mixers** — agent-stacked pytrees live on one device; gossip runs
  from the edge list of a :class:`SparseTopology` (directed edge weights plus
  self weights, planned by :func:`sparse_gossip`) for every fleet size and
  every named graph, and global averaging is a mean over the agent axis.
  The edge list is the one gossip operand: static (:func:`sparse_mixing`) or
  drawn per round by a topology process (:func:`dynamic_sparse_mixing`).

* **Collective mixers** — TPU-native path used by the launcher: gossip over a
  circulant topology (ring on the agent axis, torus over (pod, data)) becomes a
  weighted sum of ``lax.ppermute`` block rotations — pure neighbor ICI traffic,
  the whole point of the paper's agent-to-agent rounds.  Global averaging is a
  ``psum`` over the agent mesh axes — the "server" round.  Both are expressed
  with ``shard_map`` so the collectives appear explicitly in the lowered HLO
  (which the roofline analysis parses).

The probabilistic `W^k = J w.p. p else W` draw is hoisted to the host launcher
(see DESIGN.md §2): the drivers pre-draw the schedule and dispatch per round.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.topology import (
    ParticipationProcess,
    SparseTopology,
    Topology,
    TopologyProcess,
    make_topology_process,
)
from repro.utils.pytree import (
    tree_agent_krum,
    tree_agent_masked_mean,
    tree_agent_mean,
    tree_agent_median,
    tree_agent_mix_shifts,
    tree_agent_mix_sparse,
    tree_agent_trimmed_mean,
)

PyTree = Any

# ---------------------------------------------------------------------------
# Robust server-averaging rules (Byzantine-tolerant global_avg variants)
# ---------------------------------------------------------------------------

ROBUST_RULES = ("mean", "trimmed", "median", "krum")


def parse_robust_spec(spec: str):
    """``(rule, f)`` from a robust-aggregation spec string.

    Grammar mirrors the adversary/process specs: ``"mean"`` | ``"median"`` |
    ``"trimmed[:f=0.2]"`` | ``"krum[:f=0.2]"`` — ``f`` is the assumed
    Byzantine *fraction*, turned into an agent count via ``ceil(f * n)`` when
    the rule is instantiated.  Fails fast on unknown rules/keys.
    """
    head, _, tail = str(spec).partition(":")
    rule = head.strip()
    if rule not in ROBUST_RULES:
        raise ValueError(
            f"unknown robust_agg rule {rule!r}; options: {ROBUST_RULES}"
        )
    f = 0.2
    if tail:
        for item in tail.split(","):
            k, _, v = item.partition("=")
            if k.strip() != "f":
                raise ValueError(
                    f"robust_agg {rule!r} takes only 'f=<fraction>' "
                    f"(got {item!r})"
                )
            f = float(v)
    if rule in ("mean", "median") and tail:
        raise ValueError(f"robust_agg {rule!r} takes no arguments")
    if not 0.0 <= f < 0.5:
        raise ValueError(f"robust_agg fraction must be in [0, 0.5), got {f}")
    return rule, f


def make_robust_agg(spec: str, n_agents: int) -> Optional[Callable]:
    """A pluggable server-averaging rule (tree -> tree, agent-broadcast), or
    ``None`` for ``"mean"`` — the caller keeps its exact base ``global_avg``
    so the clean path stays bit-identical.  Validates that the fleet is big
    enough for the requested trim/selection margin."""
    rule, f = parse_robust_spec(spec)
    if rule == "mean":
        return None
    n_byz = int(np.ceil(f * n_agents))
    if rule == "median":
        return tree_agent_median
    if rule == "trimmed":
        if n_agents - 2 * n_byz < 1:
            raise ValueError(
                f"trimmed mean needs n - 2*ceil(f*n) >= 1 agents "
                f"(n={n_agents}, f={f} trims {n_byz} per side)"
            )
        return partial(tree_agent_trimmed_mean, trim=n_byz)
    # krum: neighbor count n - n_byz - 2 is floored at 1 inside the primitive
    return partial(tree_agent_krum, n_byz=n_byz)


@dataclasses.dataclass(frozen=True)
class MixingOps:
    """The two communication primitives Algorithm 1 needs."""

    gossip: Callable[[PyTree], PyTree]  # X -> X W
    global_avg: Callable[[PyTree], PyTree]  # X -> X J
    name: str = "custom"
    # Bytes moved per invocation per agent, filled in by the launcher for
    # communication-cost accounting (benchmarks fig4).
    gossip_edges: int = 0  # number of neighbor messages per gossip round
    # Directed neighbor messages per gossip invocation, network-wide — the
    # quantity the byte model prices.  None => derive as 2 * gossip_edges
    # (one message per direction over each undirected edge); collective
    # mixers, whose gossip_edges counts per-agent shifts, set it explicitly.
    gossip_messages: Optional[int] = None
    # Optional CompressedGossip spec (repro.core.compression).  When set,
    # ``gossip`` is already the stateless compressed form and PISCO's round
    # function threads the stateful error-feedback variant through its state;
    # the byte model prices gossip at the compressor's wire format.
    compression: Optional[Any] = None
    # Optional NetworkContext for time-varying topologies / partial
    # participation: the drivers pre-draw per-round edge weights host-side
    # and thread them through the round functions (see dynamic_sparse_mixing).
    network: Optional["NetworkContext"] = None
    # Gossip's kernel, static per run (see sparse_gossip): "offsets/K" (K
    # weighted shifts) or "edges"; None for identity and collective mixers.
    form: Optional[str] = None


def identity_mixing(n_agents: int) -> MixingOps:
    """No communication at all (an isolated baseline / ablation)."""
    return MixingOps(
        gossip=lambda t: t, global_avg=tree_agent_mean, name="identity", gossip_edges=0
    )


# ---------------------------------------------------------------------------
# Dynamic networks: the edge weights are a per-round operand
# ---------------------------------------------------------------------------


class DynamicWSlot:
    """Trace-time injection point for the per-round mixing operands.

    The algorithm builders close their round functions over
    ``MixingOps.gossip`` / ``global_avg``; for a dynamic network those
    closures read the *current* round's edge weights (and server operand)
    from this slot.  The driver stores the round's operands here immediately
    before invoking the round function **inside the same trace** (the scan
    body, or a wrapped loop round function taking them as explicit
    arguments), so the read picks up the live tracers and the compiled
    program threads the operands as real inputs — nothing is baked in as a
    constant, and no algorithm needs a signature change.
    """

    __slots__ = ("gossip_w", "server_w")

    def __init__(self):
        self.gossip_w = None
        self.server_w = None

    def set(self, gossip_w, server_w) -> None:
        self.gossip_w = gossip_w
        self.server_w = server_w


@dataclasses.dataclass(frozen=True, eq=False)
class NetworkContext:
    """Host-side bundle the drivers use to realize a dynamic network.

    Pairs the gossip-graph process with optional partial participation and
    the :class:`DynamicWSlot` the round functions read from.  ``draw_block``
    pre-draws everything a scan block needs, exactly like the Bernoulli(p)
    schedule pre-draw in :mod:`repro.core.driver`.
    """

    process: TopologyProcess
    slot: DynamicWSlot
    participation: Optional[ParticipationProcess] = None

    @property
    def n_agents(self) -> int:
        return self.process.n_agents

    def draw_block(self, start: int, stop: int):
        """``(w_gossip, w_server, messages, participants)`` for rounds
        ``[start, stop)``; operands carry a leading round axis (scan
        operands), counts are host ints for the byte accountant.

        ``w_gossip`` is the pytree ``{'edge_w': (block, 2m), 'self_w':
        (block, n)}`` over the directed base-edge order; ``w_server`` is a
        (block, n) participant mask, or without participation a (block, 1)
        placeholder — ``global_avg`` is then the exact mean and never reads
        it."""
        block = stop - start
        edge_w, self_w, messages = self.process.draw_sparse_block(start, stop)
        # duplicate per-undirected-edge weights across both orientations
        w_gossip = {
            "edge_w": np.concatenate([edge_w, edge_w], axis=1),
            "self_w": self_w,
        }
        if self.participation is None:
            w_server = np.zeros((block, 1), dtype=np.float32)
            participants = np.full(block, self.n_agents, dtype=int)
        else:
            w_server, participants = self.participation.draw_mask_block(
                start, stop
            )
        return w_gossip, w_server, messages, participants

    def draw_round(self, k: int):
        """Single-round form for the legacy loop driver."""
        wg, ws, msgs, parts = self.draw_block(k, k + 1)
        first = lambda tree: jax.tree.map(lambda a: a[0], tree)
        return first(wg), first(ws), int(msgs[0]), int(parts[0])


# ---------------------------------------------------------------------------
# Gossip from the edge list, never materializing n×n
# ---------------------------------------------------------------------------


def _directed_arrays(topo: SparseTopology):
    """Host arrays for the directed expansion of the base edge list: both
    orientations of each undirected edge (the order ``edge_w`` uses)."""
    e = topo.edges.reshape(-1, 2)
    return np.concatenate([e[:, 0], e[:, 1]]), np.concatenate([e[:, 1], e[:, 0]])


def sparse_gossip(topo: SparseTopology):
    """Plan sparse gossip on the host; returns ``(form, mix)`` with
    ``mix(tree, edge_w, self_w)`` for directed edge weights in base order.

    The directed edges group by their K distinct offsets ``d = (receiver -
    sender) mod n``; edge ``e`` fills slot ``(k_e, receivers[e])`` of a
    ``(K, n)`` weight table (a canonical edge list has no duplicate directed
    edge, so each slot holds at most one, and empty slots stay zero).

    ``"offsets/K"``: the graph is banded, so gossip is K weighted static
    shifts along the agent axis (:func:`tree_agent_mix_shifts`), no gather
    and no scatter.  Chosen when it moves fewer rows than the edge form,
    ``(K + 2) n <= 3 (2m) + 2n``: the ring, path and torus take it.
    ``"edges"``: the gather + ``segment_sum`` edge form
    (:func:`tree_agent_mix_sparse`) for graphs with many offsets, such as
    random_regular, star and erdos_renyi.  Host (numpy) ``edge_w`` builds
    the offset table on the host, so static gossip lowers to shifts alone.
    """
    n = topo.n_agents
    senders, receivers = _directed_arrays(topo)
    offsets, k_e = np.unique((receivers - senders) % n, return_inverse=True)
    offsets, k_e = offsets.tolist(), k_e.reshape(-1)
    if (len(offsets) + 2) * n > 3 * len(senders) + 2 * n:
        senders = jnp.asarray(senders, jnp.int32)
        receivers = jnp.asarray(receivers, jnp.int32)

        def mix_edges(tree, edge_w, self_w):
            return tree_agent_mix_sparse(tree, senders, receivers, edge_w, self_w, n)

        return "edges", mix_edges

    shape = (len(offsets), n)

    def mix_offsets(tree, edge_w, self_w):
        if isinstance(edge_w, np.ndarray):
            table = np.zeros(shape, np.float32)
            table[k_e, receivers] = edge_w
        else:
            table = jnp.zeros(shape, jnp.float32).at[k_e, receivers].set(edge_w)
        return tree_agent_mix_shifts(tree, offsets, table, self_w)

    return f"offsets/{len(offsets)}", mix_offsets


def sparse_mixing(topology: SparseTopology) -> MixingOps:
    """Static sparse mixers over the fixed edge list with precomputed
    Metropolis weights — O(n + m) state instead of O(n^2), numerically equal
    to ``W @ x`` over the materialized W up to float reassociation.
    Gossip runs in the form :func:`sparse_gossip` picks from the edge list:
    weighted shifts on a banded graph, gather + ``segment_sum`` otherwise."""
    form, mix = sparse_gossip(topology)
    edge_w = np.concatenate([topology.edge_weight, topology.edge_weight]).astype(
        np.float32
    )
    self_w = np.asarray(topology.self_weight, np.float32)

    def gossip(tree: PyTree) -> PyTree:
        return mix(tree, edge_w, self_w)

    return MixingOps(
        gossip=gossip,
        global_avg=tree_agent_mean,
        name=f"sparse/{topology.name}",
        gossip_edges=topology.n_edges,
        form=form,
    )


def dynamic_sparse_mixing(
    process: TopologyProcess,
    *,
    participation: float = 1.0,
    participation_seed: Optional[int] = None,
) -> MixingOps:
    """Sparse mixers over a time-varying network.

    The per-round operand is the edge-weight pytree the driver stages in the
    slot (``{'edge_w': (2m,), 'self_w': (n,)}`` in base directed-edge order,
    dropped edges zeroed) — fixed shapes, so ``lax.scan`` threads it at
    O(n + m) per round.  Partial participation uses the O(n) masked-mean
    form of the doubly stochastic sampled-to-sampled matrix S_k
    (participants average among themselves, absentees hold — the network
    mean is preserved, so gradient tracking's Lemma-1 invariant survives).
    """
    slot = DynamicWSlot()
    part = None
    if participation < 1.0:
        part = ParticipationProcess(
            process.n_agents,
            participation,
            seed=process.seed if participation_seed is None else participation_seed,
        )
    base = process.base
    form, mix = sparse_gossip(base)

    def gossip(tree: PyTree) -> PyTree:
        ops = slot.gossip_w
        return mix(tree, ops["edge_w"], ops["self_w"])

    if part is None:
        global_avg = tree_agent_mean
    else:
        def global_avg(tree: PyTree) -> PyTree:
            return tree_agent_masked_mean(tree, slot.server_w)

    name = f"sparse-dynamic/{process.spec()}/{base.name}"
    if part is not None:
        name += f"/m{part.m}of{part.n_agents}"
    return MixingOps(
        gossip=gossip,
        global_avg=global_avg,
        name=name,
        gossip_edges=base.n_edges,
        network=NetworkContext(process=process, slot=slot, participation=part),
        form=form,
    )


def make_sparse_network_mixing(
    topology: SparseTopology,
    network: Optional[str] = None,
    participation: float = 1.0,
    *,
    seed: int = 0,
) -> MixingOps:
    """Mixers for an optionally dynamic network — the one selection point
    shared by ``ExperimentSpec.make_mixing`` and the launch CLI.

    ``network=None`` with full participation is the frozen-weight path
    (:func:`sparse_mixing`); anything else routes through
    :func:`dynamic_sparse_mixing` over the parsed :class:`TopologyProcess`.
    """
    if network is None and participation >= 1.0:
        return sparse_mixing(topology)
    process = make_topology_process(network, topology, seed=seed)
    return dynamic_sparse_mixing(process, participation=participation)


# ---------------------------------------------------------------------------
# Collective mixers (shard_map + lax collectives)
# ---------------------------------------------------------------------------


def _as_tuple(x) -> tuple:
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _leaf_local_spec(spec: P) -> P:
    """Inside shard_map every mentioned axis is already local; mixing acts on
    axis 0 (the agent axis), other axes stay sharded => specs pass through."""
    return spec


def collective_global_mixing(
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    spec_tree: PyTree,
) -> MixingOps:
    """Global averaging (J) as an explicit psum over the agent mesh axes.

    ``spec_tree`` is the PartitionSpec tree of the agent-stacked state: each
    leaf spec must shard axis 0 over ``agent_axes``.
    """
    agent_axes = _as_tuple(agent_axes)
    n_agents = int(np.prod([mesh.shape[a] for a in agent_axes]))

    def avg(tree: PyTree) -> PyTree:
        def per_shard(local_tree):
            def leaf(x):
                acc = jax.lax.psum(x.astype(jnp.float32), agent_axes)
                return (acc / n_agents).astype(x.dtype)

            return jax.tree.map(leaf, local_tree)

        return jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(spec_tree,),
            out_specs=spec_tree,
            check_vma=False,
        )(tree)

    return MixingOps(
        gossip=avg,  # placeholder; callers pair this with a gossip mixer
        global_avg=avg,
        name="collective/global",
    )


def collective_shift_mixing(
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    spec_tree: PyTree,
    shifts_per_axis: dict,
    *,
    wire_dtype: Optional[str] = None,
) -> MixingOps:
    """Circulant gossip as weighted ppermute block rotations.

    ``shifts_per_axis`` maps mesh axis name -> sequence of (shift, weight)
    pairs (shift 0 = self weight; recorded on any one axis).  A ring over the
    agent axis is ``{axis: [(0, w0), (1, w1), (-1, w1)]}``; the multi-pod
    torus uses entries for both "pod" and "data".

    ``wire_dtype`` controls what goes over the wire (§Perf iteration):
    * None (default)    — permute in the state's native dtype (bf16 states
                          move bf16 bytes), accumulate the weighted combine
                          in fp32.
    * "float32"         — upcast before the permute (2x traffic for bf16
                          states; the numerically-conservative baseline).
    """
    agent_axes = _as_tuple(agent_axes)
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else None

    def gossip(tree: PyTree) -> PyTree:
        def per_shard(local_tree):
            def leaf(x):
                xw = x if wire is None else x.astype(wire)
                acc = jnp.zeros_like(x, dtype=jnp.float32)
                for axis_name, pairs in shifts_per_axis.items():
                    size = mesh.shape[axis_name]
                    for shift, weight in pairs:
                        if shift == 0:
                            continue
                        perm = [(s, (s + shift) % size) for s in range(size)]
                        moved = jax.lax.ppermute(xw, axis_name, perm)
                        if wire is None and moved.dtype != jnp.float32:
                            # keep the wire payload in the narrow dtype: the
                            # barrier stops XLA's simplifier from hoisting the
                            # f32 convert above the collective-permute
                            moved = jax.lax.optimization_barrier(moved)
                        acc = acc + weight * moved.astype(jnp.float32)
                self_w = 0.0
                for pairs in shifts_per_axis.values():
                    for shift, weight in pairs:
                        if shift == 0:
                            self_w += weight
                acc = acc + self_w * x.astype(jnp.float32)
                return acc.astype(x.dtype)

            return jax.tree.map(leaf, local_tree)

        return jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(spec_tree,),
            out_specs=spec_tree,
            check_vma=False,
        )(tree)

    g = collective_global_mixing(mesh, agent_axes, spec_tree)
    n_edges = sum(
        len([s for s, _ in pairs if s != 0]) for pairs in shifts_per_axis.values()
    )
    n_agents = int(np.prod([mesh.shape[a] for a in shifts_per_axis]))
    return MixingOps(
        gossip=gossip,
        global_avg=g.global_avg,
        name="collective/shift",
        gossip_edges=n_edges,
        # every agent ships one message per nonzero shift
        gossip_messages=n_agents * n_edges,
    )


def collective_dense_mixing(
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    spec_tree: PyTree,
    topology: Topology,
) -> MixingOps:
    """Arbitrary-W gossip on a mesh: all_gather over the agent axes + local
    weighted reduction.  Used for the paper-faithful non-circulant topologies
    (ER / path / disconnected) when running distributed."""
    agent_axes = _as_tuple(agent_axes)
    w = topology.w.astype(np.float32)
    n = topology.n_agents

    def gossip(tree: PyTree) -> PyTree:
        def per_shard(local_tree):
            # Linear agent index of this shard.
            idx = jax.lax.axis_index(agent_axes)

            def leaf(x):
                # x: (1, ...) local block.  Gather all agents' blocks, combine.
                full = jax.lax.all_gather(
                    x.astype(jnp.float32), agent_axes, axis=0, tiled=True
                )  # (n, ...)
                row = jnp.asarray(w)[idx]  # (n,)
                mixed = jnp.tensordot(row, full, axes=((0,), (0,)))
                return mixed[None].astype(x.dtype)

            return jax.tree.map(leaf, local_tree)

        return jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(spec_tree,),
            out_specs=spec_tree,
            check_vma=False,
        )(tree)

    g = collective_global_mixing(mesh, agent_axes, spec_tree)
    return MixingOps(
        gossip=gossip,
        global_avg=g.global_avg,
        name=f"collective/dense/{topology.name}",
        gossip_edges=int(topology.adj.sum()) // 2,
    )


def compressed_mixing(
    base: MixingOps,
    bits: int = 8,
) -> MixingOps:
    """Backward-compatible int-quantized gossip (the original beyond-paper
    extension).  Now a thin front for :mod:`repro.core.compression`:
    deterministic-rounding quantizer, error feedback on, mean-preserving
    difference form, byte-priced wire format.  The server round (J) stays
    exact — the expensive link gets the exact average, matching the paper's
    emphasis that server rounds drive the consensus floor.
    """
    from repro.core.compression import StochasticQuantizer, compress_mixing

    return compress_mixing(
        base, StochasticQuantizer(bits=bits, stochastic=False), error_feedback=True
    )


def hierarchical_mixing(
    mesh: jax.sharding.Mesh,
    spec_tree: PyTree,
    intra_axis: str = "data",
    inter_axes: Sequence[str] = ("pod", "data"),
    ring_weights: Sequence[float] = (0.5, 0.25, 0.25),
) -> MixingOps:
    """Beyond-paper hierarchical mode (DESIGN.md §6): gossip = ring over the
    *intra-pod* data axis only (pure ICI), server round = psum over all agent
    axes (crosses DCI).  This is HL-SGD-shaped communication with PISCO's
    gradient tracking on top."""
    w0, w1, w2 = ring_weights
    shift = {intra_axis: [(0, w0), (1, w1), (-1, w2)]}
    ops = collective_shift_mixing(mesh, inter_axes, spec_tree, shift)
    return dataclasses.replace(ops, name="collective/hierarchical")
