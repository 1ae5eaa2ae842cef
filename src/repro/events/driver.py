"""The ``events`` round driver: asynchronous execution of the round sequence.

:func:`drive_events` is the third consumer of the shared driver helpers in
:mod:`repro.core.driver` (``record_block`` / ``maybe_eval`` /
``make_block_fn``): the numerics still run as chunked on-device scans over
the registry's round functions **unchanged** — what changes is where the
per-round operands come from.  A synchronous driver draws edge weights
from the topology process and prices rounds with the barrier time model; the
events driver draws both from the :class:`~repro.events.clock.EventEngine`:

* gossip edge weights are built from the *active* edge set — realized edges
  minus those incident to agents beyond the staleness bound;
* server rounds average with the buffered aggregator's staleness weights via
  :func:`~repro.utils.pytree.tree_agent_weighted_mean`, staged through the
  same :class:`~repro.core.mixing.DynamicWSlot` mechanism as any dynamic
  network (so FedOpt server rules and compression compose untouched);
* per-round seconds come from the engine's availability clock instead of the
  barrier model.

When the engine reports ``trivial=True`` (degenerate fleet: nothing dropped,
uniform weights), :class:`Experiment` binds the ordinary spec mixing instead
of :func:`make_async_mixing` and this driver becomes ``drive_scan`` with an
engine-priced clock — the executed device program is identical, which is the
bit-exactness acceptance pin.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.algorithms import BoundAlgorithm
from repro.core.driver import (
    DEFAULT_BLOCK_SIZE,
    block_bounds,
    make_block_fn,
    maybe_eval,
    record_block,
    sample_block,
)
from repro.core.mixing import DynamicWSlot, MixingOps, sparse_gossip
from repro.core.topology import make_sparse_topology
from repro.events.clock import EventEngine
from repro.utils.pytree import tree_agent_weighted_mean

PyTree = Any


class EventNetwork:
    """Minimal network handle binding round functions to the event engine.

    ``make_block_fn`` only needs ``.slot`` to stage per-round operands inside
    the scan body; the operands themselves are drawn by the
    :class:`~repro.events.clock.EventEngine` (``drive_events`` dispatches on
    the ``events`` marker), not by a ``TopologyProcess``.
    """

    events = True
    __slots__ = ("slot",)

    def __init__(self, slot: DynamicWSlot):
        self.slot = slot


def make_async_mixing(spec: Any) -> MixingOps:
    """Mixing ops whose per-round operands are event-engine decisions.

    Gossip reads the edge-weight pytree the driver staged for the current
    round — exactly the dynamic-network slot mechanism — built by the engine
    from the staleness-masked active edge set.  The global average reads the
    engine's ``{'w', 'keep'}`` staleness weights: participants are averaged
    with the buffered aggregator's normalized weights, absentees hold.  Compression wraps on top like any
    other mixing, so the error-feedback wire path is identical.
    """
    slot = DynamicWSlot()
    n = spec.config.n_agents
    stopo = make_sparse_topology(spec.topology, n, **dict(spec.topology_kwargs))
    form, mix = sparse_gossip(stopo)

    def gossip(tree: PyTree) -> PyTree:
        ops = slot.gossip_w
        return mix(tree, ops["edge_w"], ops["self_w"])

    def global_avg(tree: PyTree) -> PyTree:
        ops = slot.server_w
        return tree_agent_weighted_mean(tree, ops["w"], ops["keep"])

    mixing = MixingOps(
        gossip=gossip,
        global_avg=global_avg,
        name=f"events/{stopo.name}",
        gossip_edges=stopo.n_edges,
        network=EventNetwork(slot),
        form=form,
    )
    if getattr(spec, "adversary", None) is not None:
        # same wrap order as ExperimentSpec.make_mixing: corruption before
        # compression, on whatever operands the engine stages (robust rules
        # are validated out for async specs, so robust_agg is a no-op here)
        from repro.core.adversary import make_adversarial_mixing

        mixing = make_adversarial_mixing(
            mixing, spec.adversary, getattr(spec, "robust_agg", "mean"),
            n_agents=n, seed=spec.config.seed,
        )
    if spec.compression is not None:
        from repro.core.compression import compress_mixing, make_compressor

        mixing = compress_mixing(
            mixing,
            make_compressor(spec.compression),
            error_feedback=spec.error_feedback,
            seed=spec.config.seed,
        )
    return mixing


def drive_events(
    bound: BoundAlgorithm,
    state,
    sampler: Callable[[int], tuple],
    rounds: int,
    hist,
    *,
    engine: EventEngine,
    eval_fn: Optional[Callable] = None,
    eval_every: int = 1,
    stop_when: Optional[Callable] = None,
    block_size: int = DEFAULT_BLOCK_SIZE,
    block_fn: Optional[Callable] = None,
):
    """Event-queue driver: scan-blocked numerics, engine-supplied operands.

    The schedule was consumed once when the engine was built — ``engine.flags``
    is the authoritative flag sequence (identical draws, in round order, to
    what the sync drivers would see), so ``bound.schedule`` is never called
    here.  Per-round simulated seconds come from the engine's availability
    clock (``record_block(..., seconds=...)`` overrides any attached barrier
    time model), and the per-agent staleness series is appended to
    ``hist.staleness`` as rounds execute.
    """
    if block_fn is None:
        block_fn = make_block_fn(bound)
    cuts = block_bounds(
        rounds,
        eval_every=eval_every if eval_fn is not None else 0,
        block_size=block_size,
    )
    net = bound.network
    staleness = getattr(hist, "staleness", None)
    for start, stop in cuts:
        flags = engine.flags[start:stop]
        local, comm = sample_block(sampler, start, stop)
        if net is None:
            realized = None
            state, metrics = block_fn(state, jnp.asarray(flags), local, comm)
        else:
            # trivial mode binds the ordinary dynamic mixing (its own
            # NetworkContext draws operands); the async mixing's EventNetwork
            # routes the draw to the engine instead.  An AdversarialNetwork
            # wrapping the EventNetwork still draws from the engine, then
            # augments the gossip operand with the block's round indices.
            inner = getattr(net, "base", net)
            if getattr(inner, "events", False):
                w_gossip, w_server, messages, participants = engine.draw_block(
                    start, stop
                )
                if inner is not net:
                    w_gossip = net.augment(w_gossip, start, stop)
            else:
                w_gossip, w_server, messages, participants = net.draw_block(
                    start, stop
                )
            realized = (messages, participants)
            state, metrics = block_fn(
                state, jnp.asarray(flags), jax.tree.map(jnp.asarray, w_gossip),
                jax.tree.map(jnp.asarray, w_server), local, comm,
            )
        rec = getattr(hist, "recorder", None)
        t_block = rec.clock_s if rec is not None else 0.0
        record_block(
            hist, metrics, flags, realized, start=start,
            seconds=engine.seconds[start:stop],
        )
        if rec is not None:
            # per-agent tracks: each agent's view of every round in the block,
            # annotated with the engine's frozen gating/participation/staleness
            # decisions — the async story the aggregate round span can't tell
            gate = engine.trace["gate"]
            parts = engine.trace["participants"]
            t0 = t_block
            for k in range(start, stop):
                dur = float(engine.seconds[k])
                f = bool(flags[k - start])
                for a in range(engine.n_agents):
                    rec.record_agent_round(
                        k, a, t0, dur, f,
                        staleness=int(engine.staleness[k, a]),
                        participant=bool(parts[k, a]),
                        gated=bool(not gate[k, a]),
                    )
                t0 += dur
        if staleness is not None:
            staleness.extend(engine.staleness[start:stop].tolist())
        maybe_eval(hist, eval_fn, eval_every, rounds, state, stop - 1)
        if stop_when is not None and stop_when(hist):
            break
    return state
