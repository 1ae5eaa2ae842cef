"""PISCO — Algorithm 1 of the paper, verbatim, over agent-stacked pytrees.

One communication round k (two stages):

  Stage 1 — T_o *local* tracked-SGD steps, zero communication (eq. 3a-3c):
      X^{k+1,t} = X^{k+1,t-1} - eta_l * Y^{k+1,t-1}
      G^{k+1,t} = stochastic grads at X^{k+1,t}
      Y^{k+1,t} = Y^{k+1,t-1} + G^{k+1,t} - G^{k+1,t-1}

  Stage 2 — one mixing round with W^k = J w.p. p else W (eq. 4a-4c):
      X^{k+1} = ((1-eta_c) X^k + eta_c (X^{k+1,T_o} - eta_l Y^{k+1,T_o})) W^k
      G^{k+1} = stochastic grads at X^{k+1} on a fresh batch
      Y^{k+1} = (Y^{k+1,T_o} + G^{k+1} - G^{k+1,T_o}) W^k

The probabilistic draw of W^k is made by the *host* trainer (uniform across
agents, i.i.d. per round — identical semantics to line 8 of Algorithm 1), which
dispatches one of two jitted round functions.  See DESIGN.md §2.

State invariant (Lemma 1, tested):  mean_i y_i == mean_i g_i  exactly, at every
round and every local step.

Update rules (DESIGN.md §10): the hardcoded ``x - eta_l * y`` descent of
eq. 3a generalizes to any :class:`repro.optim.UpdateRule` — the tracker Y is
the descent *direction*, the rule (momentum, Adam, clipped/scheduled chains)
decides the step.  ``local_opt=None`` keeps the historical inline arithmetic
bit-for-bit; ``server_opt`` adds a FedOpt-style server update (FedAvgM /
FedAdam) at global-averaging rounds, descending from the averaged previous
iterate along the round pseudo-gradient.  Lemma 1 is untouched either way:
the Y/G recursion never reads the optimizer state.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mixing import MixingOps
from repro.optim.update_rules import (
    UpdateRule,
    apply_updates,
    comm_opt_state,
    init_opt_state,
    server_step,
    sgd as _sgd_rule,
)
from repro.utils.pytree import (
    tree_add,
    tree_axpy,
    tree_scale,
    tree_sq_norm,
    tree_sub,
)

PyTree = Any
# loss_fn(params, batch) -> scalar loss for ONE agent.
LossFn = Callable[[PyTree, Any], jnp.ndarray]

# Named scopes of the round (``jax.named_scope``): they set only the
# ``op_name`` metadata of the HLO, so a profiler trace attributes device
# time to the local phase (3a-3c), the communication step (4a-4c, the mix
# excluded), each W^k mix, and the round's metrics.
SCOPE_LOCAL = "pisco.local"
SCOPE_COMM = "pisco.comm"
SCOPE_MIX = "mix"
SCOPE_METRICS = "pisco.metrics"


def _scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under the named scope ``name``."""

    def call(*args):
        with jax.named_scope(name):
            return fn(*args)

    return call


@dataclasses.dataclass(frozen=True)
class PiscoConfig:
    """Hyper-parameters of Algorithm 1."""

    n_agents: int
    t_o: int = 1  # number of local updates per round (T_o)
    eta_l: float = 0.05  # local-update step size
    eta_c: float = 1.0  # communication step size
    p: float = 0.1  # agent-to-server probability
    seed: int = 0

    def __post_init__(self):
        assert self.t_o >= 1, "T_o >= 1 (at least one local update)"
        assert 0.0 <= self.p <= 1.0


class PiscoState(NamedTuple):
    """Agent-stacked algorithm state (leading axis = n_agents on every leaf)."""

    x: PyTree  # model estimates X^k
    y: PyTree  # gradient-tracking variables Y^k
    g: PyTree  # last stochastic gradients G^k
    step: jnp.ndarray  # round counter k
    # Compressed-gossip side state: () when compression is off (zero leaves,
    # zero bytes), else {"x": residual, "y": residual, "key": PRNGKey} from
    # CompressedGossip.init_ef (see repro.core.compression).
    ef: PyTree = ()
    # Optimizer state: () when no update rules are bound (the legacy
    # hardcoded-SGD path), else {"local": agent-stacked rule state,
    # "server": FedOpt server state or ()} from optim.init_opt_state.
    opt: PyTree = ()


class RoundMetrics(NamedTuple):
    loss: jnp.ndarray  # mean over agents & local steps
    grad_sq_norm: jnp.ndarray  # ||mean_i g_i||^2 (tracked-gradient proxy)
    consensus_err: jnp.ndarray  # ||X - X_bar||_F^2 / n


def make_stacked_value_and_grad(loss_fn: LossFn) -> Callable:
    """vmap value_and_grad over the agent axis: each agent gets its own params
    slice and its own batch slice."""
    vg = jax.value_and_grad(loss_fn)
    return jax.vmap(vg, in_axes=(0, 0))


def init_state(
    loss_fn: LossFn,
    x0: PyTree,
    batch0: Any,
    local_opt: Optional[UpdateRule] = None,
    server_opt: Optional[UpdateRule] = None,
) -> PiscoState:
    """Line 2: draw Z^0 and set Y^0 = G^0 = grads(X^0; Z^0).

    ``x0`` must already be agent-stacked (typically every agent starts from the
    same point: X^0 = x^0 1^T).  When update rules are bound, their state is
    attached up front so the scan driver's carry structure is round-invariant."""
    _, g0 = make_stacked_value_and_grad(loss_fn)(x0, batch0)
    return PiscoState(
        x=x0, y=g0, g=g0, step=jnp.zeros((), jnp.int32),
        opt=init_opt_state(x0, local_opt, server_opt),
    )


def init_compression_state(state: PiscoState, mixing: MixingOps) -> PiscoState:
    """Attach error-feedback residuals when ``mixing`` carries a compressor
    (no-op otherwise); the trainer calls this right after :func:`init_state`."""
    if mixing.compression is None:
        return state
    return state._replace(ef=mixing.compression.init_ef(state.x))


def replicate_params(params: PyTree, n_agents: int) -> PyTree:
    """X^0 = x^0 1_n^T — identical start for all agents."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_agents,) + p.shape), params
    )


def _local_phase(
    stacked_vg: Callable,
    state: PiscoState,
    local_batches: Any,  # leaves shaped (T_o, n_agents, ...)
    eta_l: float,
) -> Tuple[PyTree, PyTree, PyTree, jnp.ndarray]:
    """Stage 1: lax.scan over the T_o local updates."""

    def step(carry, batch_t):
        x, y, g = carry
        x = jax.tree.map(lambda xi, yi: xi - eta_l * yi, x, y)  # (3a)
        loss, g_new = stacked_vg(x, batch_t)  # (3b)
        y = tree_add(y, tree_sub(g_new, g))  # (3c)
        return (x, y, g_new), jnp.mean(loss)

    with jax.named_scope(SCOPE_LOCAL):
        (x_to, y_to, g_to), losses = jax.lax.scan(
            step, (state.x, state.y, state.g), local_batches
        )
        return x_to, y_to, g_to, jnp.mean(losses)


def _local_phase_rule(
    stacked_vg: Callable,
    state: PiscoState,
    local_batches: Any,
    rule: UpdateRule,
    opt0: PyTree,
) -> Tuple[PyTree, PyTree, PyTree, PyTree, jnp.ndarray]:
    """Stage 1 with a pluggable update rule: the tracker Y is the descent
    direction (3a generalized), the rule turns it into a step."""

    def step(carry, batch_t):
        x, y, g, opt = carry
        upd, opt = rule.update(y, opt, x)  # (3a): direction = tracker
        x = apply_updates(x, upd)
        loss, g_new = stacked_vg(x, batch_t)  # (3b)
        y = tree_add(y, tree_sub(g_new, g))  # (3c)
        return (x, y, g_new, opt), jnp.mean(loss)

    with jax.named_scope(SCOPE_LOCAL):
        (x_to, y_to, g_to, opt), losses = jax.lax.scan(
            step, (state.x, state.y, state.g, opt0), local_batches
        )
        return x_to, y_to, g_to, opt, jnp.mean(losses)


def _consensus_error(x: PyTree) -> jnp.ndarray:
    def leaf(v):
        mean = jnp.mean(v, axis=0, keepdims=True)
        return jnp.sum((v - mean) ** 2)

    errs = jax.tree.map(leaf, x)
    return jax.tree.reduce(jnp.add, errs)


def _round_metrics(cfg, mean_loss, loss_c, g_new, x_new, compute_metrics):
    if not compute_metrics:
        z = jnp.zeros(())
        return RoundMetrics(z, z, z)
    with jax.named_scope(SCOPE_METRICS):
        gbar = jax.tree.map(lambda v: jnp.mean(v, axis=0), g_new)
        return RoundMetrics(
            loss=(mean_loss * cfg.t_o + jnp.mean(loss_c)) / (cfg.t_o + 1),
            grad_sq_norm=tree_sq_norm(gbar),
            consensus_err=_consensus_error(x_new) / cfg.n_agents,
        )


def make_round_fn(
    loss_fn: LossFn,
    cfg: PiscoConfig,
    mixing: MixingOps,
    *,
    global_round: bool,
    compute_metrics: bool = True,
    use_ef: bool = True,
    local_opt: Optional[UpdateRule] = None,
    server_opt: Optional[UpdateRule] = None,
    opt_policy: str = "mix",
) -> Callable[[PiscoState, Any, Any], Tuple[PiscoState, RoundMetrics]]:
    """Build one jittable PISCO round for a fixed W^k kind.

    The trainer compiles this twice (gossip / global) and dispatches per the
    host-side Bernoulli(p) draw.

    When ``mixing`` carries a compression spec and this is a gossip round,
    the two mixes go through the stateful error-feedback path: residuals for
    the X and Y streams ride along in ``state.ef`` (initialized by
    :func:`init_compression_state`).  ``use_ef=False`` forces the stateless
    compressed gossip instead — for callers whose state cannot carry
    residuals (the baselines in :mod:`repro.core.baselines`).

    ``local_opt`` / ``server_opt`` plug in composable update rules
    (DESIGN.md §10): the local rule replaces the hardcoded eta_l descent on
    the tracker, ``opt_policy`` ∈ {"mix", "keep", "reset"} decides what
    happens to its agent-stacked buffers at this communication round, and
    the server rule (global rounds only) applies a FedOpt-style update to
    the averaged iterate.  Both ``None`` (the default) runs the historical
    inline arithmetic — bit-identical outputs, empty opt slot.  ``state``
    must then come from :func:`init_state` with the same rules, so the opt
    slot exists up front.

    Args to the returned fn:
      state:         PiscoState
      local_batches: pytree with leaves (T_o, n_agents, ...)
      comm_batch:    pytree with leaves (n_agents, ...) — the fresh Z^{k+1}
    """
    stacked_vg = make_stacked_value_and_grad(loss_fn)
    mix = _scoped(SCOPE_MIX, mixing.global_avg if global_round else mixing.gossip)
    compressed = mixing.compression is not None and not global_round and use_ef
    has_rules = local_opt is not None or server_opt is not None
    if has_rules and local_opt is None:
        local_opt = _default_local_rule(cfg)

    def legacy_round_fn(state: PiscoState, local_batches, comm_batch):
        x_to, y_to, g_to, mean_loss = _local_phase(
            stacked_vg, state, local_batches, cfg.eta_l
        )
        with jax.named_scope(SCOPE_COMM):
            # (4a): X^{k+1} = ((1-eta_c) X^k + eta_c (X^{T_o} - eta_l Y^{T_o})) W^k
            cand = jax.tree.map(
                lambda xk, xt, yt: (1.0 - cfg.eta_c) * xk + cfg.eta_c * (xt - cfg.eta_l * yt),
                state.x,
                x_to,
                y_to,
            )
            ef = getattr(state, "ef", ())
            if compressed:
                cg = _scoped(SCOPE_MIX, mixing.compression)
                key, kx, ky = jax.random.split(ef["key"], 3)
                x_new, res_x = cg(cand, ef["x"], kx)
                # (4b): fresh-batch gradients at the mixed point
                loss_c, g_new = stacked_vg(x_new, comm_batch)
                # (4c) compressed: the difference form preserves mean_i over the
                # agent axis, so Lemma 1 (mean Y == mean G) survives exactly.
                y_new, res_y = cg(tree_add(y_to, tree_sub(g_new, g_to)), ef["y"], ky)
                ef = {"x": res_x, "y": res_y, "key": key}
            else:
                x_new = mix(cand)
                # (4b): fresh-batch gradients at the mixed point
                loss_c, g_new = stacked_vg(x_new, comm_batch)
                # (4c): Y^{k+1} = (Y^{T_o} + G^{k+1} - G^{T_o}) W^k
                y_new = mix(tree_add(y_to, tree_sub(g_new, g_to)))

        new_state = PiscoState(
            x=x_new, y=y_new, g=g_new, step=state.step + 1, ef=ef,
            opt=getattr(state, "opt", ()),
        )
        return new_state, _round_metrics(
            cfg, mean_loss, loss_c, g_new, x_new, compute_metrics
        )

    def rule_round_fn(state: PiscoState, local_batches, comm_batch):
        lopt, sopt = state.opt["local"], state.opt["server"]
        x_to, y_to, g_to, lopt, mean_loss = _local_phase_rule(
            stacked_vg, state, local_batches, local_opt, lopt
        )
        with jax.named_scope(SCOPE_COMM):
            # (4a) generalized: one more rule step along the tracker gives the
            # communicated point; eta_c interpolates against X^k as before.
            upd, lopt = local_opt.update(y_to, lopt, x_to)
            half = apply_updates(x_to, upd)
            cand = jax.tree.map(
                lambda xk, h: (1.0 - cfg.eta_c) * xk + cfg.eta_c * h,
                state.x, half,
            )
            ef = getattr(state, "ef", ())
            if compressed:
                cg = _scoped(SCOPE_MIX, mixing.compression)
                key, kx, ky = jax.random.split(ef["key"], 3)
                x_new, res_x = cg(cand, ef["x"], kx)
                loss_c, g_new = stacked_vg(x_new, comm_batch)
                y_new, res_y = cg(tree_add(y_to, tree_sub(g_new, g_to)), ef["y"], ky)
                ef = {"x": res_x, "y": res_y, "key": key}
            else:
                if global_round and server_opt is not None:
                    # FedOpt server round: descend from the averaged previous
                    # iterate along the round pseudo-gradient (DESIGN.md §10).
                    x_new, sopt = server_step(
                        server_opt, sopt, mix(state.x), mix(cand)
                    )
                else:
                    x_new = mix(cand)
                loss_c, g_new = stacked_vg(x_new, comm_batch)
                # (4c) is untouched by the rules: Lemma 1 survives any of them.
                y_new = mix(tree_add(y_to, tree_sub(g_new, g_to)))

            lopt = comm_opt_state(
                lopt, mix, cfg.n_agents, opt_policy, is_global=global_round
            )
        new_state = PiscoState(
            x=x_new, y=y_new, g=g_new, step=state.step + 1, ef=ef,
            opt={"local": lopt, "server": sopt},
        )
        return new_state, _round_metrics(
            cfg, mean_loss, loss_c, g_new, x_new, compute_metrics
        )

    return rule_round_fn if has_rules else legacy_round_fn


def _default_local_rule(cfg: PiscoConfig) -> UpdateRule:
    """The rule-path default when only ``server_opt`` is given: plain SGD at
    ``eta_l`` (bit-identical arithmetic to the hardcoded step)."""
    return _sgd_rule(cfg.eta_l)


# ---------------------------------------------------------------------------
# Special cases (paper Remarks 1 & 2)
# ---------------------------------------------------------------------------


def decentralized_config(cfg: PiscoConfig) -> PiscoConfig:
    """Remark 1: p = 0 — fully decentralized PISCO (gossip only)."""
    return dataclasses.replace(cfg, p=0.0)


def federated_config(cfg: PiscoConfig) -> PiscoConfig:
    """Remark 2: p = 1 — federated PISCO (server every round; SCAFFOLD-like)."""
    return dataclasses.replace(cfg, p=1.0)
