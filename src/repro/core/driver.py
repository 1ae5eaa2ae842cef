"""Round drivers: how communication rounds get executed on the device.

Three drivers, one contract — fill a :class:`~repro.core.trainer.History` and
return the final algorithm state:

* **loop** — the legacy per-round Python host loop: one jitted round-function
  call per round, three scalar device→host syncs per round for the metrics.
  Simple, and the reference semantics.

* **scan** — chunked ``lax.scan``: the Bernoulli(p) schedule for a *block* of
  rounds is pre-drawn on the host (identical draws, in round order, to the
  legacy loop — line 8 of Algorithm 1 is a host-side i.i.d. sequence either
  way), the block's minibatches are stacked along a new leading axis, and the
  whole block runs on-device as one ``lax.scan`` whose body dispatches between
  the gossip and global round functions with ``lax.cond``.  The host touches
  the device once per block (stacked metrics) instead of three times per
  round, and blocks are cut exactly at eval boundaries so the eval-at-x̄
  semantics match the loop round-for-round.

* **events** — the asynchronous event-queue driver (:mod:`repro.events`,
  DESIGN.md §13): round boundaries come from a simulated-clock priority
  queue over the spec's systems profile instead of a global barrier.  It
  lives in its own package and consumes this module's shared helpers
  (:func:`record_block`, :func:`maybe_eval`, :func:`make_block_fn`) — the
  third consumer, not a third copy.

All drivers duck-type the history object (``loss`` / ``grad_sq_norm`` /
``consensus_err`` / ``is_global`` lists, ``accountant``, ``byte_model``,
``eval_metrics``) so this module has no import cycle with the trainer.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithms import BoundAlgorithm

PyTree = Any
Sampler = Callable[[int], tuple]
EvalFn = Callable[[PyTree], Dict[str, float]]

DEFAULT_BLOCK_SIZE = 32

DRIVERS = ("loop", "scan", "events")


def predraw_schedule(schedule, start: int, stop: int) -> np.ndarray:
    """Materialize ``schedule(k)`` for ``k in [start, stop)`` as a bool array.

    Draws happen in round order, so a stateful :class:`BernoulliSchedule`
    yields the exact flag sequence the legacy loop would have seen."""
    return np.array([bool(schedule(k)) for k in range(start, stop)], dtype=bool)


def stack_rounds(per_round: Sequence[PyTree]) -> PyTree:
    """Stack a list of per-round batch pytrees along a new leading round axis."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_round)


def sample_block(sampler: Sampler, start: int, stop: int) -> Tuple[PyTree, PyTree]:
    """``(local, comm)`` for rounds ``[start, stop)`` with a leading round
    axis.  Samplers exposing ``sample_block(start, stop)`` (one index put +
    one device gather, e.g. :class:`repro.data.RoundSampler`) take the fast
    path; anything else falls back to per-round calls + on-device stacking."""
    fast = getattr(sampler, "sample_block", None)
    if fast is not None:
        return fast(start, stop)
    batches = [sampler(k) for k in range(start, stop)]
    return (
        stack_rounds([b[0] for b in batches]),
        stack_rounds([b[1] for b in batches]),
    )


def block_bounds(
    rounds: int, *, eval_every: int = 0, block_size: int = DEFAULT_BLOCK_SIZE,
    start: int = 0,
) -> List[Tuple[int, int]]:
    """Split ``[start, rounds)`` into scan blocks.

    Blocks end immediately after every eval round (``k % eval_every == 0`` or
    ``k == rounds - 1``; ``eval_every <= 0`` disables eval cuts) and never
    exceed ``block_size`` rounds — the only points where the driver must sync
    state to the host."""
    assert block_size >= 1
    bounds = []
    k = start
    while k < rounds:
        stop = min(k + block_size, rounds)
        if eval_every > 0:
            nxt = k if k % eval_every == 0 else (k // eval_every + 1) * eval_every
            nxt = min(nxt, rounds - 1)
            stop = min(stop, nxt + 1)
        bounds.append((k, stop))
        k = stop
    return bounds


def make_block_fn(bound: BoundAlgorithm, *, jit: bool = True) -> Callable:
    """One jitted block function scanning a block of rounds on-device:
    ``(state, flags, local, comm)`` for a static network, or
    ``(state, flags, w_gossip, w_server, local, comm)`` when ``bound.network``
    is set — the per-round mixing operands ride the scan exactly like the
    pre-drawn Bernoulli(p) flags.

    ``flags`` is the pre-drawn bool vector (block,), ``local``/``comm`` carry
    the block's batches with a leading round axis.  When the algorithm uses a
    single round function for both kinds (FedAvg, SCAFFOLD) the ``lax.cond``
    is elided.

    The jitted block donates ``state``: the carry is updated in place, so a
    block holds one copy of the agent-stacked state instead of two.  The
    state passed in is consumed; callers keep only the returned one."""
    gossip, glob = bound.gossip_round, bound.global_round
    same = glob is gossip
    net = bound.network

    if net is None:
        def body(state, per_round):
            flag, local, comm = per_round
            if same:
                return gossip(state, local, comm)
            return jax.lax.cond(flag, glob, gossip, state, local, comm)

        def block_fn(state, flags, local, comm):
            return jax.lax.scan(body, state, (flags, local, comm))
    else:
        def body(state, per_round):
            flag, w_gossip, w_server, local, comm = per_round
            # Stage this round's operands; the mixing closures inside the
            # round functions read them as live scan-operand tracers.
            net.slot.set(w_gossip, w_server)
            if same:
                return gossip(state, local, comm)
            return jax.lax.cond(flag, glob, gossip, state, local, comm)

        def block_fn(state, flags, w_gossip, w_server, local, comm):
            return jax.lax.scan(
                body, state, (flags, w_gossip, w_server, local, comm)
            )

    return jax.jit(block_fn, donate_argnums=0) if jit else block_fn


def dynamic_round_fns(
    bound: BoundAlgorithm, *, jit: bool = True
) -> Tuple[Callable, Callable]:
    """Per-round ``(gossip_fn, global_fn)`` for a dynamic network, each with
    signature ``(state, local, comm, w_gossip, w_server)``: the operands are
    explicit jit arguments (fresh per round, one trace), staged into the
    network slot before the wrapped round function is traced."""
    net = bound.network
    assert net is not None, "dynamic_round_fns requires bound.network"
    gossip, glob = bound.gossip_round, bound.global_round
    same = glob is gossip

    def wrap(fn):
        def fn_w(state, local, comm, w_gossip, w_server):
            net.slot.set(w_gossip, w_server)
            return fn(state, local, comm)

        return fn_w

    gossip_w = wrap(gossip)
    global_w = gossip_w if same else wrap(glob)
    if jit:
        gossip_w = jax.jit(gossip_w)
        global_w = gossip_w if same else jax.jit(global_w)
    return gossip_w, global_w


def _eval_at_xbar(eval_fn: EvalFn, state, k: int) -> Dict[str, float]:
    x_bar = jax.tree.map(lambda v: jnp.mean(v, axis=0), state.x)
    return dict(eval_fn(x_bar), round=k)


def _eval_agent_groups(eval_fn: EvalFn, state, k: int, mask) -> Dict[str, float]:
    """Split eval-at-x̄ by the Byzantine mask: the honest agents' consensus
    point (``honest_<key>``) vs. the faulty group's (``byz_<key>``) — the
    per-agent series a robustness run reads to see who actually converged."""
    m = np.asarray(mask, dtype=bool)
    out: Dict[str, float] = {}
    honest = jax.tree.map(lambda v: jnp.mean(v[~m], axis=0), state.x)
    for key, val in eval_fn(honest).items():
        out[f"honest_{key}"] = val
    if m.any():
        byz = jax.tree.map(lambda v: jnp.mean(v[m], axis=0), state.x)
        for key, val in eval_fn(byz).items():
            out[f"byz_{key}"] = val
    out["round"] = k
    return out


def record_flags(
    hist, flags: np.ndarray, realized=None, start: int = 0, seconds=None
) -> None:
    """Record schedule flags + per-round bytes (and simulated seconds when a
    time model is attached).  ``realized`` is an optional
    ``(messages, participants)`` pair of per-round arrays for dynamic
    networks — bytes are then priced per realized edge/participant instead of
    the static round constants.  ``start`` is the absolute index of the
    block's first round — the time model's draws are pure in ``(seed, k)``.
    ``seconds`` overrides the time model with an explicit per-round array
    (the events driver prices rounds from its own event trace).

    When the history carries a :class:`~repro.obs.trace.TraceRecorder`
    (``hist.recorder``), each round additionally becomes a span with the
    same byte/second attribution the accountant gets — recording is purely
    host-side bookkeeping over values this function already synced, so a
    ``recorder=None`` run is bit-identical by construction."""
    time_model = getattr(hist, "time_model", None)
    rec = getattr(hist, "recorder", None)
    for i, f in enumerate(flags):
        f = bool(f)
        hist.is_global.append(f)
        if realized is None:
            nbytes = hist.byte_model.round_bytes(f)
        else:
            messages, participants = realized
            nbytes = hist.byte_model.realized_round_bytes(
                f, int(messages[i]), int(participants[i])
            )
        if seconds is not None:
            sec = float(seconds[i])
        elif time_model is not None:
            sec = time_model.round_time(start + i, f)
        else:
            sec = None
        hist.accountant.record(f, nbytes, seconds=sec)
        if rec is not None:
            parts = None
            if seconds is None and time_model is not None:
                parts = time_model.round_parts(start + i, f)
            rec.record_round(start + i, f, nbytes, seconds=sec, parts=parts)


def record_block(
    hist, metrics, flags: np.ndarray, realized=None, *, start: int = 0,
    seconds=None,
) -> None:
    """One history append for a block of executed rounds — the single
    recording path every driver (loop, scan, events) funnels through:
    extends the metric series and prices flags/bytes/seconds via
    :func:`record_flags`.  ``metrics`` is a RoundMetrics pytree whose leaves
    carry a leading round axis (a loop round passes block size 1)."""
    hist.loss.extend(
        np.asarray(metrics.loss, dtype=np.float64).reshape(-1).tolist()
    )
    hist.grad_sq_norm.extend(
        np.asarray(metrics.grad_sq_norm, dtype=np.float64).reshape(-1).tolist()
    )
    hist.consensus_err.extend(
        np.asarray(metrics.consensus_err, dtype=np.float64).reshape(-1).tolist()
    )
    record_flags(hist, flags, realized, start=start, seconds=seconds)


def eval_boundary(k: int, rounds: int, eval_every: int) -> bool:
    """Whether round ``k`` is an eval round: every ``eval_every`` rounds and
    always at the final round — the one boundary rule all drivers share (the
    scan driver also cuts its blocks here so eval-at-x̄ matches the loop)."""
    return k % eval_every == 0 or k == rounds - 1


def maybe_eval(hist, eval_fn: Optional[EvalFn], eval_every: int, rounds: int,
               state, k: int) -> None:
    """Append the eval-at-x̄ readout when round ``k`` is an eval boundary;
    histories carrying an ``adversary_mask`` additionally get the
    honest-vs-Byzantine group split appended to ``eval_per_agent``."""
    if eval_fn is None or not eval_boundary(k, rounds, eval_every):
        return
    hist.eval_metrics.append(_eval_at_xbar(eval_fn, state, k))
    rec = getattr(hist, "recorder", None)
    if rec is not None:
        m = {k2: v for k2, v in hist.eval_metrics[-1].items() if k2 != "round"}
        rec.add_instant("rounds", "eval", rec.clock_s, round=k, **m)
    mask = getattr(hist, "adversary_mask", None)
    if mask is not None:
        hist.eval_per_agent.append(_eval_agent_groups(eval_fn, state, k, mask))


def drive_scan(
    bound: BoundAlgorithm,
    state,
    sampler: Sampler,
    rounds: int,
    hist,
    *,
    eval_fn: Optional[EvalFn] = None,
    eval_every: int = 1,
    stop_when: Optional[Callable] = None,
    block_size: int = DEFAULT_BLOCK_SIZE,
    block_fn: Optional[Callable] = None,
):
    """Chunked-scan driver.  ``stop_when`` is consulted at block boundaries
    (the only host-visible points), so a stop may overshoot by at most one
    block relative to the legacy loop.  Pass a prebuilt ``block_fn`` (from
    :func:`make_block_fn`) to reuse its jit cache across drives."""
    if block_fn is None:
        block_fn = make_block_fn(bound)
    cuts = block_bounds(
        rounds,
        eval_every=eval_every if eval_fn is not None else 0,
        block_size=block_size,
    )
    net = bound.network
    for start, stop in cuts:
        flags = predraw_schedule(bound.schedule, start, stop)
        local, comm = sample_block(sampler, start, stop)
        if net is None:
            realized = None
            state, metrics = block_fn(state, jnp.asarray(flags), local, comm)
        else:
            w_gossip, w_server, messages, participants = net.draw_block(start, stop)
            realized = (messages, participants)
            # tree-mapped: the gossip operand is an edge-weight pytree
            state, metrics = block_fn(
                state, jnp.asarray(flags), jax.tree.map(jnp.asarray, w_gossip),
                jax.tree.map(jnp.asarray, w_server), local, comm,
            )
        # one device->host sync for the whole block
        record_block(hist, metrics, flags, realized, start=start)
        maybe_eval(hist, eval_fn, eval_every, rounds, state, stop - 1)
        if stop_when is not None and stop_when(hist):
            break
    return state


def drive_loop(
    bound: BoundAlgorithm,
    state,
    sampler: Sampler,
    rounds: int,
    hist,
    *,
    eval_fn: Optional[EvalFn] = None,
    eval_every: int = 1,
    stop_when: Optional[Callable] = None,
    jit: bool = True,
    round_fns: Optional[Tuple[Callable, Callable]] = None,
):
    """The legacy per-round host loop (reference semantics).  ``round_fns``
    supplies prejitted ``(gossip_fn, global_fn)`` to reuse across drives —
    when ``bound.network`` is set they must be the operand-threaded form from
    :func:`dynamic_round_fns`."""
    net = bound.network
    if round_fns is not None:
        gossip_fn, global_fn = round_fns
    elif net is not None:
        gossip_fn, global_fn = dynamic_round_fns(bound, jit=jit)
    else:
        gossip_fn, global_fn = bound.gossip_round, bound.global_round
        if jit:
            gossip_fn = jax.jit(gossip_fn)
            global_fn = (
                jax.jit(global_fn)
                if global_fn is not bound.gossip_round else gossip_fn
            )
    for k in range(rounds):
        local_batches, comm_batch = sampler(k)
        is_global = bool(bound.schedule(k))
        fn = global_fn if is_global else gossip_fn
        if net is None:
            realized = None
            state, metrics = fn(state, local_batches, comm_batch)
        else:
            w_gossip, w_server, messages, participants = net.draw_round(k)
            state, metrics = fn(
                state, local_batches, comm_batch,
                jax.tree.map(jnp.asarray, w_gossip),
                jax.tree.map(jnp.asarray, w_server),
            )
            realized = ([messages], [participants])
        record_block(
            hist, metrics, np.array([is_global]), realized, start=k
        )
        maybe_eval(hist, eval_fn, eval_every, rounds, state, k)
        if stop_when is not None and stop_when(hist):
            break
    return state


def get_driver(name: str) -> Callable:
    if name == "scan":
        return drive_scan
    if name == "loop":
        return drive_loop
    if name == "events":
        # local import: the event-queue subsystem builds on this module
        from repro.events.driver import drive_events

        return drive_events
    raise ValueError(f"unknown driver {name!r}; options: {DRIVERS}")
