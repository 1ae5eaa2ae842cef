"""Declarative experiments: ``ExperimentSpec`` + ``Experiment``.

An :class:`ExperimentSpec` is a frozen, dict/JSON-round-trippable bundle of
*what* to run — algorithm name (resolved through the registry), topology,
dynamic-network process (``network=``) and server-round participation
fraction, compression, :class:`~repro.core.pisco.PiscoConfig`, round budget,
eval policy, and which round driver executes it.  The *problem* (loss function,
initial parameters, data sampler, eval function) stays runtime state on
:class:`Experiment`, because closures and datasets don't belong in JSON.

::

    spec = ExperimentSpec.create(algo="pisco", n_agents=10, t_o=5, p=0.1,
                                 eta_l=0.3, rounds=100, eval_every=10)
    exp = Experiment(spec, loss_fn=loss_fn, params0=params0,
                     sampler_factory=make_sampler, eval_fn=eval_fn)
    hist = exp.run()                    # -> History
    hists = exp.sweep(seeds=[0, 1, 2])  # vmapped multi-seed, one device program
    grid = exp.sweep(grid={"p": [0.0, 0.1, 1.0]})  # list of (spec, History)

Multi-seed sweeps vmap the scanned round block over a leading seed axis —
every seed advances in lockstep through the *same* realized communication
schedule (the spec's seed draws it), while data sampling and anything else the
``sampler_factory`` keys off ``spec.seed`` vary per seed.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adversary import (
    adversary_mask,
    make_adversarial_mixing,
    parse_adversary_spec,
    unwrap_network,
)
from repro.core.algorithms import BoundAlgorithm, get_algorithm
from repro.core.compression import make_byte_model, make_compressor, compress_mixing
from repro.core.driver import (
    DEFAULT_BLOCK_SIZE,
    DRIVERS,
    _eval_agent_groups,
    record_flags,
    block_bounds,
    drive_loop,
    drive_scan,
    make_block_fn,
    predraw_schedule,
    sample_block,
    stack_rounds,
)
from repro.core.mixing import MixingOps, make_robust_agg, make_sparse_network_mixing
from repro.core.pisco import LossFn, PiscoConfig, replicate_params
from repro.core.topology import make_sparse_topology, parse_process_spec
from repro.core.trainer import History, record_wall_time
from repro.optim.update_rules import (
    OPT_POLICIES,
    make_lr_schedule,
    parse_update_rule,
    resolve_update_rules,
)

PyTree = Any
Sampler = Callable[[int], tuple]
EvalFn = Callable[[PyTree], Dict[str, float]]

_CONFIG_FIELDS = tuple(f.name for f in dataclasses.fields(PiscoConfig))


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """Everything declarative about one training run."""

    algo: str
    config: PiscoConfig
    topology: str = "ring"
    topology_kwargs: Tuple[Tuple[str, Any], ...] = ()
    # Dynamic network: None => the frozen base weights every round (legacy
    # path, bit-identical to pre-dynamic runs); else a TopologyProcess spec —
    # "static" | "bernoulli[:failure_prob]" | "matching" | "roundrobin[:n]".
    network: Optional[str] = None
    # Fraction of agents sampled into each server round (uniform m-of-n,
    # doubly stochastic sampled-to-sampled averaging); 1.0 => everyone.
    participation: float = 1.0
    # Neighbor-sampled cohorts: fraction of agents seeding each gossip round
    # (only the subgraph incident to the cohort is active; sugar for
    # network="cohort:<frac>", mutually exclusive with an explicit network).
    cohort: Optional[float] = None
    # Simulated systems-cost profile (repro.sim, DESIGN.md §11): a named
    # heterogeneity scenario — "uniform" | "lognormal-stragglers" |
    # "edge-vs-datacenter" | "wan-gossip" | "lan-gossip" — with optional
    # k=v overrides ("uniform:latency=0,bw=inf,rtt=0").  When set, every
    # executed round is priced in simulated seconds (History.sim_time_s)
    # alongside bytes; None (the default, and what every legacy payload
    # deserializes to) records no sim time — bit-identical behavior.
    systems: Optional[str] = None
    # Asynchronous execution config (repro.events, DESIGN.md §13), as a spec
    # string "<rule>[:k=v,...]" over rules constant|poly|buffer with keys
    # alpha / bound / buffer — e.g. "poly:alpha=0.5,bound=2,buffer=4".  Only
    # meaningful with driver="events" (which also requires a systems
    # profile); None (the default, and what every legacy payload deserializes
    # to) means constant weights, no staleness bound, no server buffer.
    async_: Optional[str] = None
    # Byzantine fault injection (repro.core.adversary, DESIGN.md §14): an
    # AdversaryProcess spec — "signflip[:f=..,scale=..]" |
    # "random:f=..,scale=.." | "collusion:f=..,target=drift" — corrupting the
    # selected agents' outgoing gossip payloads and server uploads, pure in
    # (seed, round).  None (the default, and what every legacy payload
    # deserializes to) injects nothing — bit-identical behavior.
    adversary: Optional[str] = None
    # Server-averaging rule at global rounds: "mean" (the default plain
    # average — bit-identical legacy path) | "trimmed[:f=..]" | "median" |
    # "krum[:f=..]".  Robust rules need full participation and sync
    # aggregation (participation=1.0, async_=None).
    robust_agg: str = "mean"
    compression: Optional[str] = None  # None | "q8" | "q4" | "top0.1" | ...
    error_feedback: bool = True
    # Pluggable update rules (DESIGN.md §10), as declarative strings:
    # optimizer        — local rule ("sgd" | "momentum[:beta=..]" | "adam" |
    #                    "clip:1.0|momentum" | ...); None => the registry
    #                    entry's default, which for the built-ins is the
    #                    bit-exact legacy hardcoded-SGD path.
    # server_optimizer — FedOpt server rule at global-averaging rounds
    #                    ("fedavgm" | "fedadam" | "sgd:lr=..." | ...).
    # lr_schedule      — per-round local-LR decay over optim.schedules
    #                    ("linear[:final=..]" | "cosine" | "warmup_cosine").
    # opt_policy       — opt-state comm policy override ("mix"|"keep"|"reset").
    optimizer: Optional[str] = None
    server_optimizer: Optional[str] = None
    lr_schedule: Optional[str] = None
    opt_policy: Optional[str] = None
    rounds: int = 100
    eval_every: int = 1
    # "scan" (on-device blocks) | "loop" (legacy) | "events" (async event
    # queue over the systems profile, repro.events)
    driver: str = "scan"
    block_size: int = DEFAULT_BLOCK_SIZE

    def __post_init__(self):
        if self.driver not in DRIVERS:
            raise ValueError(f"driver {self.driver!r} not in {DRIVERS}")
        # fail fast on malformed optimizer specs (cheap parse, discarded)
        if self.optimizer is not None:
            parse_update_rule(self.optimizer)
        if self.server_optimizer is not None:
            parse_update_rule(self.server_optimizer)
        if self.lr_schedule is not None:
            make_lr_schedule(self.lr_schedule, 1.0, 1)
        if self.opt_policy is not None and self.opt_policy not in OPT_POLICIES:
            raise ValueError(
                f"opt_policy {self.opt_policy!r} not in {OPT_POLICIES}"
            )
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(
                f"participation must be in (0, 1], got {self.participation}"
            )
        if self.cohort is not None:
            if not 0.0 < self.cohort <= 1.0:
                raise ValueError(f"cohort must be in (0, 1], got {self.cohort}")
            if self.network is not None:
                raise ValueError(
                    "cohort is sugar for network='cohort:<frac>'; pass one, not both"
                )
        if self.network is not None:
            parse_process_spec(self.network)  # fail fast on bad specs
        if self.systems is not None:
            # local import: repro.sim imports the Experiment API
            from repro.sim.profiles import parse_systems_spec

            parse_systems_spec(self.systems)  # fail fast on bad profiles
        if self.async_ is not None:
            from repro.events.staleness import parse_async_spec

            parse_async_spec(self.async_)  # fail fast on bad async specs
            if self.driver != "events":
                raise ValueError(
                    "async_ only applies to driver='events' "
                    f"(got driver={self.driver!r})"
                )
        if self.adversary is not None:
            # full probe: validates grammar AND that f leaves an honest agent
            parse_adversary_spec(
                self.adversary, self.config.n_agents, self.config.seed
            )
        # probe the robust rule (validates grammar + that trimming leaves
        # agents); robust rules replace the participation-aware server
        # average wholesale, so they need the synchronous full fleet
        if make_robust_agg(self.robust_agg, self.config.n_agents) is not None:
            if self.participation != 1.0:
                raise ValueError(
                    f"robust_agg={self.robust_agg!r} needs participation=1.0 "
                    f"(got {self.participation}) — robust rules aggregate the "
                    "full fleet"
                )
            if self.async_ is not None:
                raise ValueError(
                    f"robust_agg={self.robust_agg!r} needs synchronous server "
                    f"rounds (async_=None, got {self.async_!r})"
                )
        if self.driver == "events" and self.systems is None:
            raise ValueError(
                "driver='events' needs a systems profile (spec.systems) — "
                "the event clock is drawn from the fleet realization"
            )
        # normalize mapping-typed topology kwargs into sorted item tuples so
        # specs stay hashable and JSON round-trips are canonical
        if isinstance(self.topology_kwargs, dict):
            object.__setattr__(
                self, "topology_kwargs", tuple(sorted(self.topology_kwargs.items()))
            )
        get_algorithm(self.algo)  # fail fast on unknown algorithms

    @classmethod
    def create(cls, algo: str = "pisco", **kw) -> "ExperimentSpec":
        """Flat constructor: PiscoConfig fields may be passed directly
        (``ExperimentSpec.create(algo="pisco", n_agents=10, p=0.1, ...)``).
        The retired key ``sparse`` is accepted and dropped: every spec
        gossips from the edge list."""
        kw.pop("sparse", None)
        cfg_kw = {k: kw.pop(k) for k in list(kw) if k in _CONFIG_FIELDS}
        return cls(algo=algo, config=PiscoConfig(**cfg_kw), **kw)

    def replace(self, **kw) -> "ExperimentSpec":
        """`dataclasses.replace` that also routes PiscoConfig field names
        (``spec.replace(p=0.3)``) into the nested config."""
        cfg_kw = {k: kw.pop(k) for k in list(kw) if k in _CONFIG_FIELDS}
        spec = self
        if cfg_kw:
            spec = dataclasses.replace(
                spec, config=dataclasses.replace(spec.config, **cfg_kw)
            )
        return dataclasses.replace(spec, **kw) if kw else spec

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["topology_kwargs"] = dict(self.topology_kwargs)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentSpec":
        d = dict(d)
        d.pop("sparse", None)  # retired key of older payloads
        d["config"] = PiscoConfig(**d["config"])
        d["topology_kwargs"] = tuple(sorted(dict(d.get("topology_kwargs", {})).items()))
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(s))

    # -- derived pieces -----------------------------------------------------

    @property
    def effective_network(self) -> Optional[str]:
        """The network process spec after ``cohort`` sugar is expanded."""
        if self.cohort is not None:
            return f"cohort:{self.cohort:g}"
        return self.network

    def make_mixing(self) -> MixingOps:
        stopo = make_sparse_topology(
            self.topology, self.config.n_agents, **dict(self.topology_kwargs)
        )
        mixing = make_sparse_network_mixing(
            stopo, self.effective_network, self.participation,
            seed=self.config.seed,
        )
        # fault injection + robust server rule wrap BEFORE compression, so
        # corruption rides the compressed wire stream (Byzantine agents
        # corrupt what they transmit); the clean spec returns mixing as-is
        mixing = make_adversarial_mixing(
            mixing, self.adversary, self.robust_agg,
            n_agents=self.config.n_agents, seed=self.config.seed,
        )
        if self.compression is not None:
            mixing = compress_mixing(
                mixing,
                make_compressor(self.compression),
                error_feedback=self.error_feedback,
                seed=self.config.seed,
            )
        return mixing


class Experiment:
    """A spec plus the runtime problem pieces; ``run()`` produces a History.

    ``sampler_factory(spec)`` builds a fresh per-round sampler for a spec (the
    hook multi-seed sweeps use); a plain ``sampler`` works for single runs.
    ``mixing`` overrides the spec-derived mixer — the hook the launcher uses
    to swap in collective (shard_map) mixers.
    """

    def __init__(
        self,
        spec: ExperimentSpec,
        *,
        loss_fn: LossFn,
        params0: Optional[PyTree] = None,
        x0: Optional[PyTree] = None,
        sampler: Optional[Sampler] = None,
        sampler_factory: Optional[Callable[[ExperimentSpec], Sampler]] = None,
        eval_fn: Optional[EvalFn] = None,
        mixing: Optional[MixingOps] = None,
        stop_when: Optional[Callable[[History], bool]] = None,
        recorder: Any = None,
    ):
        if (params0 is None) == (x0 is None):
            raise ValueError("pass exactly one of params0 (unstacked) or x0 (stacked)")
        if (sampler is None) == (sampler_factory is None):
            raise ValueError("pass exactly one of sampler or sampler_factory")
        self.spec = spec
        self.loss_fn = loss_fn
        self._params0 = params0
        self._x0 = x0
        self._sampler = sampler
        self._sampler_factory = sampler_factory
        self.eval_fn = eval_fn
        self._mixing = mixing
        self.stop_when = stop_when
        # Optional repro.obs TraceRecorder: threaded onto each History so the
        # drivers' recording funnel emits spans.  Deliberately NOT part of
        # _pieces() — grid sweeps build fresh Experiments and must not share
        # (and interleave onto) one recorder timeline.
        self.recorder = recorder

    # -- plumbing -----------------------------------------------------------

    def _pieces(self) -> dict:
        return dict(
            loss_fn=self.loss_fn,
            params0=self._params0,
            x0=self._x0,
            sampler=self._sampler,
            sampler_factory=self._sampler_factory,
            eval_fn=self.eval_fn,
            mixing=self._mixing,
            stop_when=self.stop_when,
        )

    def _make_sampler(self, spec: ExperimentSpec) -> Sampler:
        if self._sampler_factory is not None:
            return self._sampler_factory(spec)
        return self._sampler

    def _x0_stacked(self) -> PyTree:
        if self._x0 is not None:
            return self._x0
        return replicate_params(self._params0, self.spec.config.n_agents)

    def _bind(self, mixing: MixingOps) -> BoundAlgorithm:
        spec = self.spec
        opt_kw = resolve_update_rules(
            spec.optimizer, spec.server_optimizer, spec.lr_schedule,
            spec.opt_policy,
            eta_l=spec.config.eta_l, rounds=spec.rounds, t_o=spec.config.t_o,
        )
        return get_algorithm(spec.algo).bind(
            self.loss_fn, spec.config, mixing, **opt_kw
        )

    def _fresh_history(self, mixing: MixingOps, bound: BoundAlgorithm) -> History:
        hist = History(
            byte_model=make_byte_model(
                mixing,
                self._x0_stacked(),
                self.spec.config.n_agents,
                mixes_per_round=bound.comm.mixes_per_round,
                server_payloads=bound.comm.server_payloads,
            )
        )
        if self.spec.systems is not None and self.spec.driver != "events":
            # local import: repro.sim imports the Experiment API
            from repro.sim.costmodel import make_time_model

            # pricing sees the base network (unwrap_network): Byzantine
            # agents send wrong bytes, not different byte/time counts
            hist.time_model = make_time_model(
                self.spec, hist.byte_model, network=unwrap_network(mixing.network)
            )
        hist.adversary_mask = adversary_mask(
            self.spec.adversary, self.spec.config.n_agents, self.spec.config.seed
        )
        return hist

    # -- execution ----------------------------------------------------------

    def run(self) -> History:
        spec = self.spec
        if spec.driver == "events":
            return self._run_events()
        mixing = self._mixing if self._mixing is not None else spec.make_mixing()
        bound = self._bind(mixing)
        sampler = self._make_sampler(spec)
        _, comm0 = sampler(-1)
        state = bound.init(self.loss_fn, self._x0_stacked(), comm0)
        hist = self._fresh_history(mixing, bound)
        # single runs only: seed sweeps share device programs but must not
        # interleave many seeds onto one recorder timeline
        hist.recorder = self.recorder
        drive = drive_scan if spec.driver == "scan" else drive_loop
        kw = {"block_size": spec.block_size} if spec.driver == "scan" else {}
        with record_wall_time(hist):
            state = drive(
                bound, state, sampler, spec.rounds, hist,
                eval_fn=self.eval_fn, eval_every=spec.eval_every,
                stop_when=self.stop_when, **kw,
            )
        hist.final_state = state
        return hist

    def _run_events(self) -> History:
        """The events-driver execution path (DESIGN.md §13).

        The event clock needs the whole flag sequence up front (staleness is
        a property of the entire schedule), so the stateful Bernoulli(p)
        schedule is pre-drawn exactly once in round order — the same draws
        the sync drivers would have made.  When the realized fleet makes the
        run **trivial** (no staleness drops, exactly uniform aggregation
        weights — any degenerate uniform/free-link profile), the ordinary
        spec mixing is bound and the executed device program is bit-identical
        to ``driver="scan"``; otherwise the staleness-aware async mixing
        carries the engine's per-round decisions into the numerics.
        """
        from repro.events.clock import make_event_engine
        from repro.events.driver import drive_events, make_async_mixing

        spec = self.spec
        mixing = self._mixing if self._mixing is not None else spec.make_mixing()
        bound = self._bind(mixing)
        flags = predraw_schedule(bound.schedule, 0, spec.rounds)
        byte_model = make_byte_model(
            mixing,
            self._x0_stacked(),
            spec.config.n_agents,
            mixes_per_round=bound.comm.mixes_per_round,
            server_payloads=bound.comm.server_payloads,
        )
        engine = make_event_engine(
            spec, byte_model, flags,
            network=unwrap_network(getattr(mixing, "network", None)),
        )
        if not engine.trivial:
            mixing = make_async_mixing(spec)
            bound = self._bind(mixing)
        sampler = self._make_sampler(spec)
        _, comm0 = sampler(-1)
        state = bound.init(self.loss_fn, self._x0_stacked(), comm0)
        hist = History(byte_model=byte_model)
        hist.recorder = self.recorder
        hist.event_trace = engine.trace
        hist.adversary_mask = adversary_mask(
            spec.adversary, spec.config.n_agents, spec.config.seed
        )
        with record_wall_time(hist):
            state = drive_events(
                bound, state, sampler, spec.rounds, hist,
                engine=engine, eval_fn=self.eval_fn,
                eval_every=spec.eval_every, stop_when=self.stop_when,
                block_size=spec.block_size,
            )
        hist.final_state = state
        return hist

    def sweep(
        self,
        seeds: Optional[Sequence[int]] = None,
        grid: Optional[Dict[str, Sequence[Any]]] = None,
    ):
        """Either a vmapped multi-seed run (``seeds=[...]`` -> list of History,
        one per seed, all seeds advanced on-device in one scanned program) or a
        sequential hyper-parameter grid (``grid={"p": [...], ...}`` -> list of
        ``(spec, History)`` over the cartesian product)."""
        if (seeds is None) == (grid is None):
            raise ValueError("pass exactly one of seeds or grid")
        if grid is not None:
            out = []
            for combo in itertools.product(*grid.values()):
                spec = self.spec.replace(**dict(zip(grid.keys(), combo)))
                out.append((spec, Experiment(spec, **self._pieces()).run()))
            return out
        return self._sweep_seeds(list(seeds))

    def _sweep_seeds(self, seeds: List[int]) -> List[History]:
        if self._sampler_factory is None:
            raise ValueError("sweep(seeds=...) needs a sampler_factory")
        if self.spec.driver == "events":
            raise ValueError(
                "sweep(seeds=...) does not support driver='events'; "
                "run per-seed via sweep(grid={'seed': [...]}) instead"
            )
        spec = self.spec
        n_seeds = len(seeds)
        mixing = self._mixing if self._mixing is not None else spec.make_mixing()
        bound = self._bind(mixing)
        samplers = [self._make_sampler(spec.replace(seed=s)) for s in seeds]

        def stacked_sampler(k: int):
            batches = [s(k) for s in samplers]
            return (
                stack_rounds([b[0] for b in batches]),
                stack_rounds([b[1] for b in batches]),
            )

        # Seed axis in front of everything the round functions touch: states
        # and batches are vmapped, the schedule flag broadcasts.
        x0 = self._x0_stacked()
        x0_s = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n_seeds,) + v.shape), x0
        )
        _, comm0 = stacked_sampler(-1)
        state = jax.vmap(lambda x, b: bound.init(self.loss_fn, x, b))(x0_s, comm0)
        same = bound.global_round is bound.gossip_round
        vgossip = jax.vmap(bound.gossip_round)
        vbound = dataclasses.replace(
            bound,
            gossip_round=vgossip,
            global_round=vgossip if same else jax.vmap(bound.global_round),
        )
        block_fn = make_block_fn(vbound)

        hists = [self._fresh_history(mixing, bound) for _ in seeds]
        cuts = block_bounds(
            spec.rounds,
            eval_every=spec.eval_every if self.eval_fn is not None else 0,
            block_size=spec.block_size,
        )
        net = bound.network
        with record_wall_time(*hists):
            for start, stop in cuts:
                flags = predraw_schedule(bound.schedule, start, stop)
                per_seed = [sample_block(s, start, stop) for s in samplers]
                # (block, seeds, ...) — round axis scans, seed axis vmaps
                local = jax.tree.map(
                    lambda *ls: jnp.stack(ls, axis=1), *[b[0] for b in per_seed]
                )
                comm = jax.tree.map(
                    lambda *ls: jnp.stack(ls, axis=1), *[b[1] for b in per_seed]
                )
                if net is None:
                    realized = None
                    state, metrics = block_fn(state, jnp.asarray(flags), local, comm)
                else:
                    # all seeds advance through the same realized network (like
                    # the shared schedule); the operands broadcast across the
                    # vmapped seed axis as scan-body closure constants
                    wg, ws, messages, participants = net.draw_block(start, stop)
                    realized = (messages, participants)
                    state, metrics = block_fn(
                        state, jnp.asarray(flags), jax.tree.map(jnp.asarray, wg),
                        jax.tree.map(jnp.asarray, ws), local, comm,
                    )
                loss = np.asarray(metrics.loss, dtype=np.float64)  # (block, seeds)
                gsq = np.asarray(metrics.grad_sq_norm, dtype=np.float64)
                cerr = np.asarray(metrics.consensus_err, dtype=np.float64)
                k_end = stop - 1
                do_eval = self.eval_fn is not None and (
                    k_end % spec.eval_every == 0 or k_end == spec.rounds - 1
                )
                for i, hist in enumerate(hists):
                    hist.loss.extend(loss[:, i].tolist())
                    hist.grad_sq_norm.extend(gsq[:, i].tolist())
                    hist.consensus_err.extend(cerr[:, i].tolist())
                    record_flags(hist, flags, realized, start=start)
                    if do_eval:
                        x_bar = jax.tree.map(
                            lambda v: jnp.mean(v[i], axis=0), state.x
                        )
                        hist.eval_metrics.append(
                            dict(self.eval_fn(x_bar), round=k_end)
                        )
                        if hist.adversary_mask is not None:
                            state_i = jax.tree.map(lambda v: v[i], state)
                            hist.eval_per_agent.append(_eval_agent_groups(
                                self.eval_fn, state_i, k_end,
                                hist.adversary_mask,
                            ))
        for i, hist in enumerate(hists):
            hist.final_state = jax.tree.map(lambda v: v[i], state)
        return hists


def run_experiment(spec: ExperimentSpec, **pieces) -> History:
    """One-shot convenience: ``run_experiment(spec, loss_fn=..., ...)``."""
    return Experiment(spec, **pieces).run()
