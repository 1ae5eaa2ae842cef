"""First-class algorithm registry — the experiment-facing protocol layer.

Every semi-decentralized protocol the repo can train (PISCO and the Table-1/2
baselines, plus any third-party addition) is one :class:`Algorithm` entry:

* a **builder** closing the round functions over ``(loss_fn, cfg, mixing)``,
* a declarative **default schedule** (``"bernoulli"`` / ``"never"`` /
  ``"always"`` / ``"periodic"`` — line 8 of Algorithm 1 and its degenerate
  cases), and
* a :class:`CommProfile` pricing the protocol's traffic *as data*: how many
  mixing invocations a gossip round performs (gradient tracking mixes both the
  X and Y streams; plain-SGD families mix X only) and how many payloads one
  server exchange moves per direction (SCAFFOLD ships the model *and* the
  control variate).

Registering a new protocol is one file anywhere downstream::

    from repro.core.algorithms import BoundAlgorithm, register_algorithm

    @register_algorithm("my_algo", mixes_per_round=1)
    def _build(spec, loss_fn, cfg, mixing, **_):
        return my_init, my_gossip_round, my_global_round

— no trainer edits, no byte-model edits, no benchmark edits.  The trainer,
the :class:`~repro.core.experiment.Experiment` API, and the benchmark harness
all resolve algorithms exclusively through :func:`get_algorithm`.

Round-function contract (shared with PISCO, see :mod:`repro.core.pisco`)::

    init(loss_fn, x0_stacked, comm_batch0) -> state
    round_fn(state, local_batches, comm_batch) -> (state, RoundMetrics)

``gossip_round`` and ``global_round`` must return identical pytree
structures/dtypes — the scan driver dispatches between them with ``lax.cond``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import baselines as B
from repro.core.mixing import MixingOps
from repro.core.pisco import (
    LossFn,
    PiscoConfig,
    init_compression_state,
    init_state,
    make_round_fn,
)
from repro.core.schedule import PeriodicSchedule, make_schedule
from repro.optim.update_rules import OPT_POLICIES, UpdateRule, parse_update_rule

PyTree = Any
# builder(spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0
#         [, local_opt=None, server_opt=None, opt_policy="..."])
#   -> (init, gossip_round, global_round)
# The optimizer kwargs are only passed when update rules are actually bound,
# so legacy builders (and third-party registrations) keep working unchanged.
Builder = Callable[..., Tuple[Callable, Callable, Callable]]

SCHEDULE_KINDS = ("bernoulli", "never", "always", "periodic")


@dataclasses.dataclass(frozen=True)
class CommProfile:
    """Per-protocol communication cost, priced as data (no byte-model edits).

    ``mixes_per_round``   — mixing invocations per communication round; each
                            gossip mix moves one message per directed edge.
    ``server_payloads``   — payloads one agent moves per direction of a server
                            exchange (model only = 1; model + control variate
                            or tracking stream = 2).
    ``server_based``      — every communication round is agent-to-server.
    ``uses_local_updates``— the protocol consumes the T_o local batches.
    """

    mixes_per_round: int = 1
    server_payloads: int = 1
    server_based: bool = False
    uses_local_updates: bool = True


@dataclasses.dataclass(frozen=True)
class BoundAlgorithm:
    """An :class:`Algorithm` closed over ``(loss_fn, cfg, mixing)`` — what the
    round drivers actually run."""

    name: str
    init: Callable[[LossFn, PyTree, Any], Any]
    gossip_round: Callable
    global_round: Callable
    schedule: Callable[[int], bool]
    comm: CommProfile
    # NetworkContext when the mixing is dynamic (time-varying topology and/or
    # partial participation): the drivers pre-draw per-round operands through
    # it and thread them into the round functions.  None => static network,
    # the exact pre-dynamic code path.
    network: Optional[Any] = None
    # The resolved update rules this binding runs (None/None => the legacy
    # hardcoded-SGD arithmetic) and the opt-state communication policy.
    local_opt: Optional[UpdateRule] = None
    server_opt: Optional[UpdateRule] = None
    opt_policy: str = "mix"


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """One registry entry: builder + declarative schedule + comm profile.

    ``avg_period`` (periodic schedules only) is the explicit server-averaging
    period H used when ``cfg.p == 0`` gives no implied period; Gossip-PGA's
    documented default is H = 10 [CYZ+21].  When ``cfg.p > 0`` the period is
    derived as ``round(1/p)`` so a Bernoulli(p) PISCO run and a periodic
    baseline spend the same expected server budget.
    """

    name: str
    build: Builder
    comm: CommProfile = CommProfile()
    schedule: str = "bernoulli"
    avg_period: int = 10
    description: str = ""
    # Default update rules, as declarative strings parsed at bind time
    # (None => the legacy hardcoded-SGD path); ``opt_policy`` is what happens
    # to agent-stacked optimizer buffers at communication rounds (DESIGN.md
    # §10): "mix" with the round's W/J, "keep" local, or "reset" at server
    # synchronizations.
    local_opt: Optional[str] = None
    server_opt: Optional[str] = None
    opt_policy: str = "mix"

    def __post_init__(self):
        if self.schedule not in SCHEDULE_KINDS:
            raise ValueError(
                f"schedule {self.schedule!r} not in {SCHEDULE_KINDS}"
            )
        if self.opt_policy not in OPT_POLICIES:
            raise ValueError(
                f"opt_policy {self.opt_policy!r} not in {OPT_POLICIES}"
            )

    def make_default_schedule(self, cfg: PiscoConfig):
        if self.schedule == "never":
            return make_schedule(0.0)
        if self.schedule == "always":
            return make_schedule(1.0)
        if self.schedule == "periodic":
            period = (
                max(1, int(round(1.0 / cfg.p))) if cfg.p > 0 else self.avg_period
            )
            return PeriodicSchedule(period)
        return make_schedule(cfg.p, cfg.seed)

    def bind(
        self,
        loss_fn: LossFn,
        cfg: PiscoConfig,
        mixing: MixingOps,
        *,
        eta: Optional[float] = None,
        eta_g: float = 1.0,
        schedule: Optional[Callable[[int], bool]] = None,
        local_opt: Optional[Any] = None,
        server_opt: Optional[Any] = None,
        opt_policy: Optional[str] = None,
    ) -> BoundAlgorithm:
        """Close the algorithm over a concrete problem; ``schedule`` overrides
        the declarative default (e.g. a replayed flag sequence).

        ``local_opt`` / ``server_opt`` accept an :class:`UpdateRule` or its
        declarative string form, overriding the registry entry's defaults;
        both unresolved (the default) runs the legacy hardcoded-SGD
        arithmetic bit-for-bit.  When rules are bound, the comm profile is
        re-priced as data: a server rule ships one extra payload per
        direction (the previous averaged iterate feeding the pseudo-
        gradient), and the "mix" policy moves each params-shaped optimizer
        buffer through the network alongside the model.
        """
        lo = local_opt if local_opt is not None else self.local_opt
        so = server_opt if server_opt is not None else self.server_opt
        policy = opt_policy if opt_policy is not None else self.opt_policy
        if policy not in OPT_POLICIES:
            raise ValueError(f"opt_policy {policy!r} not in {OPT_POLICIES}")
        if isinstance(lo, str):
            lo = parse_update_rule(lo, lr=cfg.eta_l if eta is None else eta)
        if isinstance(so, str):
            so = parse_update_rule(so, lr=eta_g)
        if so is not None and lo is None:
            # a server rule alone still runs the rule path; materialize the
            # default local rule so init and round functions agree on state
            lo = parse_update_rule("sgd", lr=cfg.eta_l if eta is None else eta)

        opt_kw = {}
        comm = self.comm
        if lo is not None or so is not None:
            opt_kw = dict(local_opt=lo, server_opt=so, opt_policy=policy)
            if so is not None:
                comm = dataclasses.replace(
                    comm, server_payloads=comm.server_payloads + 1
                )
            n_buffers = lo.n_buffers if lo is not None else 0
            if n_buffers and policy == "mix":
                comm = dataclasses.replace(
                    comm,
                    mixes_per_round=comm.mixes_per_round + n_buffers,
                    server_payloads=comm.server_payloads + n_buffers,
                )
        init, gossip, glob = self.build(
            self, loss_fn, cfg, mixing, eta=eta, eta_g=eta_g, **opt_kw
        )
        return BoundAlgorithm(
            name=self.name,
            init=_owned_init(init),
            gossip_round=gossip,
            global_round=glob,
            schedule=schedule if schedule is not None else
            self.make_default_schedule(cfg),
            comm=comm,
            network=getattr(mixing, "network", None),
            local_opt=lo,
            server_opt=so,
            opt_policy=policy,
        )


def _owned_init(init: Callable) -> Callable:
    """Wrap an algorithm's ``init`` so the state owns each of its buffers:
    no leaf is one of the caller's ``x0``/``batch0`` arrays or another leaf
    (``y = g = G^0`` at init).  The scan driver donates the state to each
    block, and a buffer can be donated only once and only if nothing else
    still reads it."""

    def init_owned(loss_fn, x0, batch0):
        state = init(loss_fn, x0, batch0)
        seen = {id(v) for v in jax.tree.leaves((x0, batch0))}

        def own(v):
            if id(v) in seen:
                return jnp.copy(v)
            seen.add(id(v))
            return v

        return jax.tree.map(own, state)

    return init_owned


_REGISTRY: Dict[str, Algorithm] = {}


def register_algorithm(
    name: str,
    *,
    mixes_per_round: int = 1,
    server_payloads: Optional[int] = None,
    server_based: bool = False,
    uses_local_updates: bool = True,
    schedule: str = "bernoulli",
    avg_period: int = 10,
    local_opt: Optional[str] = None,
    server_opt: Optional[str] = None,
    opt_policy: str = "mix",
    description: str = "",
) -> Callable[[Builder], Builder]:
    """Decorator registering a builder under ``name``.

    ``server_payloads`` defaults to ``mixes_per_round`` — a protocol that
    mixes two streams over gossip links generally ships both streams through
    the server too (PISCO/DSGT move X and Y; SCAFFOLD the model and variate).

    ``local_opt`` / ``server_opt`` are default update-rule strings (e.g. a
    PISCO-M entry would register ``local_opt="momentum"``); ``opt_policy``
    is the entry's opt-state communication policy when rules are bound.
    """

    def deco(build: Builder) -> Builder:
        if name in _REGISTRY:
            raise ValueError(f"algorithm {name!r} already registered")
        _REGISTRY[name] = Algorithm(
            name=name,
            build=build,
            comm=CommProfile(
                mixes_per_round=mixes_per_round,
                server_payloads=(
                    mixes_per_round if server_payloads is None else server_payloads
                ),
                server_based=server_based,
                uses_local_updates=uses_local_updates,
            ),
            schedule=schedule,
            avg_period=avg_period,
            local_opt=local_opt,
            server_opt=server_opt,
            opt_policy=opt_policy,
            description=description or (build.__doc__ or "").strip(),
        )
        return build

    return deco


def unregister_algorithm(name: str) -> None:
    """Remove a registry entry (tests / plugin reload)."""
    _REGISTRY.pop(name, None)


def get_algorithm(name: str) -> Algorithm:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def registered_algorithms() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# The paper's seven protocols, ported onto the registry
# ---------------------------------------------------------------------------


@register_algorithm(
    "pisco",
    mixes_per_round=2,
    description="PISCO (Algorithm 1): tracked local updates + Bernoulli(p) server",
)
def _build_pisco(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="mix",
):
    del spec, eta, eta_g
    opt_kw = dict(local_opt=local_opt, server_opt=server_opt, opt_policy=opt_policy)
    return (
        lambda lf, x0, b0: init_compression_state(
            init_state(lf, x0, b0, local_opt, server_opt), mixing
        ),
        make_round_fn(loss_fn, cfg, mixing, global_round=False, **opt_kw),
        make_round_fn(loss_fn, cfg, mixing, global_round=True, **opt_kw),
    )


@register_algorithm(
    "periodical_gt",
    mixes_per_round=2,
    schedule="never",
    description="Periodical-GT [LLKS24]: PISCO with p = 0 (gossip every round)",
)
def _build_periodical_gt(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="mix",
):
    del spec, eta, eta_g
    fn = B.make_periodical_gt_round_fn(
        loss_fn, cfg, mixing,
        local_opt=local_opt, server_opt=server_opt, opt_policy=opt_policy,
    )
    # init_state (not dsgt_init): the round fn carries a PiscoState, and the
    # scan driver needs the carry pytree type to match it exactly.
    def init(lf, x0, b0):
        return init_state(lf, x0, b0, local_opt, server_opt)

    return init, fn, fn


@register_algorithm(
    "dsgt",
    mixes_per_round=2,
    uses_local_updates=False,
    description="DSGT [PN21]: gradient tracking, one step per round",
)
def _build_dsgt(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="mix",
):
    del spec, eta_g
    eta = cfg.eta_l if eta is None else eta
    opt_kw = dict(local_opt=local_opt, server_opt=server_opt, opt_policy=opt_policy)

    def init(lf, x0, b0):
        return B.dsgt_init(lf, x0, b0, local_opt, server_opt)

    return (
        init,
        B.make_dsgt_round_fn(loss_fn, eta, mixing, global_round=False, **opt_kw),
        B.make_dsgt_round_fn(loss_fn, eta, mixing, global_round=True, **opt_kw),
    )


def _build_dsgd_family(loss_fn, cfg, mixing, eta, local_opt, server_opt, opt_policy):
    opt_kw = dict(local_opt=local_opt, server_opt=server_opt, opt_policy=opt_policy)

    def init(lf, x0, b0):
        return B.dsgd_init(lf, x0, b0, local_opt, server_opt)

    return (
        init,
        B.make_dsgd_round_fn(
            loss_fn, eta, mixing, global_round=False, t_o=cfg.t_o, **opt_kw
        ),
        B.make_dsgd_round_fn(
            loss_fn, eta, mixing, global_round=True, t_o=cfg.t_o, **opt_kw
        ),
    )


@register_algorithm(
    "dsgd",
    mixes_per_round=1,
    uses_local_updates=False,
    schedule="never",
    description="DSGD [NO09]: gossip SGD",
)
def _build_dsgd(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="mix",
):
    del spec, eta_g
    eta = cfg.eta_l if eta is None else eta
    return _build_dsgd_family(
        loss_fn, cfg, mixing, eta, local_opt, server_opt, opt_policy
    )


@register_algorithm(
    "gossip_pga",
    mixes_per_round=1,
    uses_local_updates=False,
    schedule="periodic",
    avg_period=10,
    description="Gossip-PGA [CYZ+21]: gossip SGD + periodic global averaging",
)
def _build_gossip_pga(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="mix",
):
    del spec, eta_g
    eta = cfg.eta_l if eta is None else eta
    return _build_dsgd_family(
        loss_fn, cfg, mixing, eta, local_opt, server_opt, opt_policy
    )


@register_algorithm(
    "fedavg",
    mixes_per_round=1,
    server_based=True,
    schedule="always",
    opt_policy="reset",
    description="FedAvg [MMR+17]: local SGD + server averaging every round",
)
def _build_fedavg(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="reset",
):
    del spec, eta_g
    eta = cfg.eta_l if eta is None else eta

    def init(lf, x0, b0):
        return B.dsgd_init(lf, x0, b0, local_opt, server_opt)

    s = B.make_dsgd_round_fn(
        loss_fn, eta, mixing, global_round=True, t_o=cfg.t_o,
        local_opt=local_opt, server_opt=server_opt, opt_policy=opt_policy,
    )
    return init, s, s


@register_algorithm(
    "scaffold",
    mixes_per_round=2,
    server_based=True,
    schedule="always",
    opt_policy="reset",
    description="SCAFFOLD [KKM+20]: model + control variate per server exchange",
)
def _build_scaffold(
    spec, loss_fn, cfg, mixing, *, eta=None, eta_g=1.0,
    local_opt=None, server_opt=None, opt_policy="reset",
):
    del spec, eta

    def init(lf, x0, b0):
        return B.scaffold_init(lf, x0, b0, local_opt, server_opt)

    fn = B.make_scaffold_round_fn(
        loss_fn, cfg.eta_l, eta_g, cfg.t_o, mixing,
        local_opt=local_opt, server_opt=server_opt, opt_policy=opt_policy,
    )
    return init, fn, fn
