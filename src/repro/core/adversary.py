"""Byzantine fault injection + robust aggregation (ROADMAP item 4).

PISCO's robustness story is stressed here with *actively faulty* agents
rather than merely heterogeneous ones: an :class:`AdversaryProcess` corrupts
the selected agents' **outgoing communication payloads** — both gossip
messages and server uploads — while their local compute stays honest (the
corruption is on the wire, which is what a Byzantine peer controls).

Like the topology processes, everything is a pure function of the spec:

* *which* agents are Byzantine is drawn once from the domain-separated
  ``np.random.default_rng((_ADV_TAG, seed))`` stream (pure in ``seed``);
* *what* they send in round ``k`` is pure in ``(seed, k)`` — kinds needing
  per-round randomness fold the round index into an on-device PRNG key, and
  the round index rides the drivers' existing per-round operand path
  (:class:`~repro.core.mixing.DynamicWSlot`), so every driver (loop, scan at
  any block boundary, events) sees identical corruption.

The counterpart is the pluggable **robust server-averaging rule**
(``robust_agg=``): coordinate-wise trimmed mean, coordinate median, or
Krum-style selection (:mod:`repro.utils.pytree` primitives, selected by
:func:`repro.core.mixing.make_robust_agg`) replacing the plain mean at
global-averaging rounds.  Both features compose as a :class:`MixingOps`
wrapper (:func:`make_adversarial_mixing`) — round functions, byte/time
accounting, and compression are untouched: corruption happens *before* the
wire compressor (Byzantine agents corrupt what they transmit) and the robust
rule replaces ``global_avg`` (which compression never touches).

See DESIGN.md §14 for where gradient tracking's Lemma-1 invariant survives
(clean runs, exactly) and where it breaks (any corrupted or non-mean
aggregate — by design: that breakage is what the robust rules trade for
bounded aggregate error).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mixing import MixingOps, make_robust_agg

PyTree = Any

_ADV_TAG = 0xB12A  # domain separation for the Byzantine-set draw

ADVERSARY_KINDS = ("signflip", "random", "collusion")


@dataclasses.dataclass(frozen=True)
class AdversaryProcess:
    """Which agents are Byzantine and what they put on the wire.

    ``kind``:

    * ``signflip``  — corrupted payloads are ``-scale * x`` (the classic
      gradient/model sign-flip attack);
    * ``random``    — corrupted payloads are ``scale``-sized Gaussian noise,
      re-drawn each round (pure in ``(seed, round)`` via ``fold_in``);
    * ``collusion`` — all Byzantine agents transmit the *same* drifted value
      (the fleet mean plus ``scale`` along a fixed seed-drawn unit
      direction), the coordinated attack plain averaging cannot outvote.
    """

    kind: str
    f: float = 0.2
    scale: float = 1.0
    target: str = "drift"
    n_agents: int = 1
    seed: int = 0

    def __post_init__(self):
        if self.kind not in ADVERSARY_KINDS:
            raise ValueError(
                f"unknown adversary kind {self.kind!r}; "
                f"options: {ADVERSARY_KINDS}"
            )
        if not 0.0 < self.f < 1.0:
            raise ValueError(f"adversary fraction must be in (0, 1), got {self.f}")
        if self.kind == "collusion" and self.target != "drift":
            raise ValueError(
                f"collusion target {self.target!r} not supported (only 'drift')"
            )
        if self.n_byz >= self.n_agents:
            raise ValueError(
                f"f={self.f} makes all {self.n_agents} agents Byzantine — "
                "at least one honest agent is required"
            )

    @property
    def n_byz(self) -> int:
        return int(np.ceil(self.f * self.n_agents))

    @property
    def needs_round(self) -> bool:
        """Whether corruption depends on the round index (and therefore needs
        the per-round operand thread through the drivers)."""
        return self.kind == "random"

    def spec(self) -> str:
        s = f"{self.kind}:f={self.f:g}"
        if self.scale != 1.0:
            s += f",scale={self.scale:g}"
        if self.kind == "collusion":
            s += f",target={self.target}"
        return s

    def mask(self) -> np.ndarray:
        """(n_agents,) bool — True where Byzantine.  Pure in ``seed``."""
        rng = np.random.default_rng((_ADV_TAG, int(self.seed)))
        byz = rng.choice(self.n_agents, size=self.n_byz, replace=False)
        out = np.zeros(self.n_agents, dtype=bool)
        out[byz] = True
        return out

    # -- on-device corruption ----------------------------------------------

    def make_corrupt(self) -> Callable[[PyTree, Any], PyTree]:
        """``corrupt(tree, k)`` mapping an agent-stacked payload pytree to its
        on-the-wire form: honest rows pass through bit-exactly, Byzantine
        rows are replaced per ``kind``.  ``k`` is the (possibly traced) round
        index; kinds with round-independent corruption ignore it."""
        maskj = jnp.asarray(self.mask())
        scale = float(self.scale)
        base_key = jax.random.fold_in(
            jax.random.PRNGKey(int(self.seed) & 0x7FFFFFFF), _ADV_TAG
        )

        def rowmask(x):
            return maskj.reshape((-1,) + (1,) * (x.ndim - 1))

        if self.kind == "signflip":

            def corrupt(tree: PyTree, k=None) -> PyTree:
                def leaf(x):
                    xf = x.astype(jnp.float32)
                    return jnp.where(rowmask(x), -scale * xf, xf).astype(x.dtype)

                return jax.tree.map(leaf, tree)

        elif self.kind == "random":

            def corrupt(tree: PyTree, k) -> PyTree:
                kr = jax.random.fold_in(base_key, jnp.asarray(k, jnp.int32))
                leaves, treedef = jax.tree.flatten(tree)
                out = []
                for i, x in enumerate(leaves):
                    noise = scale * jax.random.normal(
                        jax.random.fold_in(kr, i), x.shape, jnp.float32
                    )
                    out.append(
                        jnp.where(rowmask(x), noise, x.astype(jnp.float32))
                        .astype(x.dtype)
                    )
                return jax.tree.unflatten(treedef, out)

        else:  # collusion: one common drifted value across all Byzantine rows

            def corrupt(tree: PyTree, k=None) -> PyTree:
                leaves, treedef = jax.tree.flatten(tree)
                out = []
                for i, x in enumerate(leaves):
                    d = jax.random.normal(
                        jax.random.fold_in(base_key, i), x.shape[1:], jnp.float32
                    )
                    d = d / jnp.maximum(
                        jnp.linalg.norm(d.reshape(-1)), jnp.float32(1e-12)
                    )
                    xf = x.astype(jnp.float32)
                    target = jnp.mean(xf, axis=0, keepdims=True) + scale * d[None]
                    out.append(jnp.where(rowmask(x), target, xf).astype(x.dtype))
                return jax.tree.unflatten(treedef, out)

        return corrupt


def parse_adversary_spec(
    spec: str, n_agents: int = 1, seed: int = 0
) -> AdversaryProcess:
    """``AdversaryProcess`` from ``"kind[:k=v,...]"`` — e.g.
    ``"signflip:f=0.2"``, ``"random:f=0.1,scale=5"``,
    ``"collusion:f=0.25,target=drift"``.  Fails fast on unknown kinds/keys
    (ExperimentSpec validates at construction with a 1-honest-agent probe)."""
    head, _, tail = str(spec).partition(":")
    kw: dict = {}
    if tail:
        for item in tail.split(","):
            key, eq, v = item.partition("=")
            key = key.strip()
            if not eq or key not in ("f", "scale", "target"):
                raise ValueError(
                    f"bad adversary argument {item!r} in {spec!r} "
                    "(keys: f, scale, target)"
                )
            kw[key] = v if key == "target" else float(v)
    return AdversaryProcess(
        kind=head.strip(), n_agents=n_agents, seed=seed, **kw
    )


def adversary_mask(
    spec: Optional[str], n_agents: int, seed: int = 0
) -> Optional[List[bool]]:
    """The Byzantine mask for a spec string (None passes through) — the form
    :class:`~repro.core.trainer.History` records for per-agent eval."""
    if spec is None:
        return None
    return [bool(b) for b in parse_adversary_spec(spec, n_agents, seed).mask()]


# ---------------------------------------------------------------------------
# Per-round operand plumbing: the round index as a scan operand
# ---------------------------------------------------------------------------


class _AdvSlot:
    """Trace-time slot holding the current round index (a live tracer inside
    scan bodies) for round-dependent corruption."""

    __slots__ = ("k",)

    def __init__(self):
        self.k = None


class _CompositeSlot:
    """Slot facade the drivers stage into: splits the augmented gossip
    operand ``{"w": <base>, "adv_k": k}`` between the adversary slot and the
    wrapped network's own slot (if any)."""

    __slots__ = ("base", "adv")

    def __init__(self, base, adv: _AdvSlot):
        self.base = base
        self.adv = adv

    def set(self, w_gossip, w_server) -> None:
        self.adv.k = w_gossip["adv_k"]
        if self.base is not None:
            self.base.set(w_gossip["w"], w_server)


class AdversarialNetwork:
    """Network handle threading the round index through the drivers.

    Wraps the base mixing's network context (or stands alone over a static
    mixing): ``draw_block`` delegates to the base draw — identical message /
    participant counts, so byte and time pricing cannot tell an adversarial
    run from a clean one — and augments the gossip operand with the block's
    round indices.  Pricing paths unwrap via :func:`unwrap_network`.
    """

    adversarial = True

    def __init__(self, base, n_agents: int, static_messages: int):
        self.base = base
        self.n_agents = n_agents
        self._static_messages = int(static_messages)
        self.adv_slot = _AdvSlot()
        self.slot = _CompositeSlot(
            None if base is None else base.slot, self.adv_slot
        )

    def augment(self, w_gossip, start: int, stop: int):
        """Wrap a base gossip operand with the rounds' indices (the events
        driver calls this on engine-drawn operands)."""
        return {
            "w": w_gossip,
            "adv_k": np.arange(start, stop, dtype=np.int32),
        }

    def draw_block(self, start: int, stop: int):
        block = stop - start
        if self.base is None:
            w_gossip = np.zeros((block, 1), dtype=np.float32)
            w_server = np.zeros((block, 1), dtype=np.float32)
            messages = np.full(block, self._static_messages, dtype=int)
            participants = np.full(block, self.n_agents, dtype=int)
        else:
            w_gossip, w_server, messages, participants = self.base.draw_block(
                start, stop
            )
        return self.augment(w_gossip, start, stop), w_server, messages, participants

    def draw_round(self, k: int):
        wg, ws, msgs, parts = self.draw_block(k, k + 1)
        first = lambda tree: jax.tree.map(lambda a: a[0], tree)
        return first(wg), first(ws), int(msgs[0]), int(parts[0])


def unwrap_network(net):
    """The base network context pricing/engine code should see — the
    adversarial wrapper changes numerics only, never costs."""
    return net.base if isinstance(net, AdversarialNetwork) else net


# ---------------------------------------------------------------------------
# The MixingOps wrapper
# ---------------------------------------------------------------------------


def make_adversarial_mixing(
    base: MixingOps,
    adversary: Optional[str] = None,
    robust_agg: str = "mean",
    *,
    n_agents: int,
    seed: int = 0,
) -> MixingOps:
    """Wrap any mixing with fault injection and/or a robust server rule.

    * ``gossip``      becomes corrupt-then-mix: Byzantine rows are replaced
      on the wire, then the base gossip (static, dynamic, collective) runs
      unchanged.  Wrapping happens *before* compression, so under a
      compressed spec the corruption rides the compressed wire stream.
    * ``global_avg``  becomes corrupt-then-aggregate, where the aggregate is
      the base rule for ``robust_agg="mean"`` or a robust rule (trimmed /
      median / krum) otherwise.  Robust rules assume full participation
      (``ExperimentSpec`` validates).

    ``adversary=None`` with ``robust_agg="mean"`` returns ``base`` itself —
    the clean path is bit-identical by construction.  Accounting metadata
    (``gossip_edges`` / ``gossip_messages`` / realized counts) is preserved:
    Byzantine agents send *wrong* bytes, not fewer.
    """
    adv = (
        parse_adversary_spec(adversary, n_agents, seed)
        if adversary is not None
        else None
    )
    robust = make_robust_agg(robust_agg, n_agents)
    if adv is None and robust is None:
        return base

    agg = robust if robust is not None else base.global_avg
    name = base.name
    net = base.network
    if adv is None:
        new_gossip, new_global = base.gossip, agg
    else:
        corrupt = adv.make_corrupt()
        base_gossip = base.gossip
        if adv.needs_round:
            static_messages = (
                base.gossip_messages
                if base.gossip_messages is not None
                else 2 * base.gossip_edges
            )
            adv_net = AdversarialNetwork(base.network, n_agents, static_messages)
            net = adv_net
            get_k = lambda: adv_net.adv_slot.k
        else:
            get_k = lambda: None

        def new_gossip(tree: PyTree) -> PyTree:
            return base_gossip(corrupt(tree, get_k()))

        def new_global(tree: PyTree) -> PyTree:
            return agg(corrupt(tree, get_k()))

        name += f"/adv:{adv.spec()}"
    if robust is not None:
        name += f"/robust:{robust_agg}"
    return dataclasses.replace(
        base, gossip=new_gossip, global_avg=new_global, name=name, network=net
    )
