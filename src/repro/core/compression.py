"""Compressed gossip: quantization / sparsification for the communication path.

The paper saves communication *rounds* (small ``p``, ``T_o`` local steps); this
module adds the orthogonal axis the Conclusions defer to future work — saving
*bytes per round* — following the compressed decentralized methods of
[ZLL+22 / Li et al.] and the peer-to-peer-aided setting of FedDec.

Three pieces:

* :class:`Compressor` — per-agent-message lossy codecs (stochastic
  quantization to int8/int4, top-k sparsification, identity).  Every
  compressor also *prices* itself: :meth:`Compressor.wire_bits` returns the
  exact bits one agent ships per message, which feeds the byte-level
  accounting in :mod:`repro.core.schedule`.

* :class:`CompressedGossip` — wraps any :class:`MixingOps.gossip` in the
  **mean-preserving difference form**

      out_i = x_i + sum_j W_ij q(m_j) - q(m_i),        m_i = x_i (+ e_i)

  Because W is doubly stochastic, ``mean_i out_i == mean_i x_i`` *exactly*,
  for any compressor — so gradient tracking's Lemma-1 invariant
  (``mean_i y_i == mean_i g_i``) survives compression of Y.  With error
  feedback the residual ``e_i`` accumulates what q dropped and is re-offered
  next round, restoring convergence for biased compressors (top-k).

* :func:`compress_mixing` / :func:`make_byte_model` — glue: attach a
  compressor to existing :class:`MixingOps` (edge-list or collective), and build
  the closed-form :class:`RoundByteModel` the trainer charges per round.

The compressed path is *opt-in*: a ``MixingOps`` without a ``compression``
spec runs the exact same code as before (bit-identical outputs).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mixing import MixingOps
from repro.core.schedule import RoundByteModel
from repro.utils.pytree import tree_add, tree_sub, tree_zeros_like

PyTree = Any

SCALE_BITS = 32  # one fp32 scale per (leaf, agent) message row
INDEX_BITS = 32  # one int32 coordinate per surviving top-k entry


# ---------------------------------------------------------------------------
# Compressors
# ---------------------------------------------------------------------------


class Compressor:
    """Lossy codec for one agent-stacked leaf (axis 0 = agents).

    ``compress(x, key)`` returns the *dequantized* wire values (what the
    receiving neighbors reconstruct) with the same shape/dtype as ``x``;
    compression is applied independently per agent row, since each agent
    encodes its own outgoing message.  ``key=None`` selects deterministic
    rounding (used by kernels/tests); a PRNGKey enables stochastic modes.
    """

    name: str = "abstract"

    def compress(self, x: jnp.ndarray, key=None) -> jnp.ndarray:
        raise NotImplementedError

    def wire_bits(self, n_elements: int, itemsize_bits: int = 32) -> int:
        """Exact wire bits for one agent's message of ``n_elements`` scalars
        from a single leaf (including scale/index side channels)."""
        raise NotImplementedError

    def compress_tree(self, tree: PyTree, key=None) -> PyTree:
        flat, treedef = jax.tree.flatten(tree)
        if key is None:
            keys = [None] * len(flat)
        else:
            keys = list(jax.random.split(key, len(flat)))
        out = [self.compress(x, k) for x, k in zip(flat, keys)]
        return jax.tree.unflatten(treedef, out)


@dataclasses.dataclass(frozen=True)
class IdentityCompressor(Compressor):
    """Full precision — the pricing baseline (and the 'disabled' codec)."""

    name: str = "fp32"

    def compress(self, x, key=None):
        return x

    def wire_bits(self, n_elements: int, itemsize_bits: int = 32) -> int:
        return n_elements * itemsize_bits


def _agent_rows(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[0], -1)


@dataclasses.dataclass(frozen=True)
class StochasticQuantizer(Compressor):
    """QSGD-style symmetric quantizer, per-agent-row max-abs scaling.

    ``bits`` ∈ {4, 8}: signed grid {-qmax..qmax}, qmax = 2^(bits-1) - 1.
    Deterministic mode rounds to nearest (error ≤ scale/2 per element);
    stochastic mode rounds up/down with probability proportional to the
    fractional part, making the codec unbiased: E[q(x)] = x.
    """

    bits: int = 8
    stochastic: bool = True

    def __post_init__(self):
        assert self.bits in (4, 8), "int8 / int4 wire formats only"

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"q{self.bits}" + ("s" if self.stochastic else "")

    @property
    def qmax(self) -> float:
        return float(2 ** (self.bits - 1) - 1)

    def compress(self, x, key=None):
        rows = _agent_rows(x).astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=1, keepdims=True), 1e-12)
        scale = scale / self.qmax
        u = rows / scale
        if self.stochastic and key is not None:
            noise = jax.random.uniform(key, rows.shape)
            q = jnp.floor(u + noise)
        else:
            q = jnp.round(u)
        q = jnp.clip(q, -self.qmax, self.qmax)
        return (q * scale).reshape(x.shape).astype(x.dtype)

    def wire_bits(self, n_elements: int, itemsize_bits: int = 32) -> int:
        return n_elements * self.bits + SCALE_BITS


@dataclasses.dataclass(frozen=True)
class TopKCompressor(Compressor):
    """Keep the ``fraction`` largest-magnitude coordinates per agent row.

    Biased (contractive): ||x - q(x)||² ≤ (1 - k/d) ||x||², which is exactly
    the δ-contraction error feedback needs.  Wire format: (value, index)
    pairs, fp32 + int32 each.
    """

    fraction: float = 0.1

    def __post_init__(self):
        assert 0.0 < self.fraction <= 1.0

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"top{self.fraction:g}"

    def k_for(self, n_elements: int) -> int:
        return max(1, int(math.ceil(self.fraction * n_elements)))

    def compress(self, x, key=None):
        rows = _agent_rows(x)
        d = rows.shape[1]
        k = self.k_for(d)
        _, idx = jax.lax.top_k(jnp.abs(rows), k)  # (n, k)
        mask = jnp.zeros_like(rows, dtype=bool)
        mask = mask.at[jnp.arange(rows.shape[0])[:, None], idx].set(True)
        return jnp.where(mask, rows, 0).reshape(x.shape).astype(x.dtype)

    def wire_bits(self, n_elements: int, itemsize_bits: int = 32) -> int:
        return self.k_for(n_elements) * (itemsize_bits + INDEX_BITS)


_REGISTRY: dict = {
    "none": lambda: IdentityCompressor(),
    "fp32": lambda: IdentityCompressor(),
    "q8": lambda: StochasticQuantizer(bits=8),
    "q4": lambda: StochasticQuantizer(bits=4),
    "q8d": lambda: StochasticQuantizer(bits=8, stochastic=False),
    "q4d": lambda: StochasticQuantizer(bits=4, stochastic=False),
}


def make_compressor(spec: str) -> Compressor:
    """Parse 'none' | 'q8' | 'q4' | 'q8d' | 'q4d' | 'topK' (K a fraction)."""
    if spec in _REGISTRY:
        return _REGISTRY[spec]()
    if spec.startswith("top"):
        try:
            fraction = float(spec[3:])
        except ValueError:
            raise ValueError(
                f"unknown compressor spec {spec!r} (top-k needs a fraction, "
                f"e.g. 'top0.1')"
            ) from None
        return TopKCompressor(fraction=fraction)
    raise ValueError(
        f"unknown compressor spec {spec!r}; options: "
        f"{sorted(_REGISTRY)} or 'top<fraction>'"
    )


# ---------------------------------------------------------------------------
# Mean-preserving compressed gossip (+ error feedback)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompressedGossip:
    """Difference-form compressed gossip over a base mixing operator.

    Stateful form (:meth:`__call__`) threads an error-feedback residual and a
    PRNG key through the round function; :meth:`stateless` is the keyless,
    residual-free variant used by baseline round functions that cannot carry
    extra state.  Both preserve the agent mean exactly, for any ``gamma``.

    ``gamma`` is the CHOCO-SGD consensus step size applied to the
    compressed correction:  out = x + γ (W q(m) − q(m)).  γ = 1 is the
    undamped form (fine for quantizers, whose error is a fraction of a
    quantization step); aggressive contractive compressors (small-k top-k)
    need γ < 1 or the error-feedback loop can diverge under large local
    steps — see DESIGN.md §7.
    """

    base_gossip: Callable[[PyTree], PyTree]
    compressor: Compressor
    error_feedback: bool = True
    seed: int = 0
    gamma: float = 1.0

    def init_ef(self, template: PyTree) -> dict:
        """Per-stream residuals (X and Y are mixed separately each round)."""
        res = tree_zeros_like(template) if self.error_feedback else ()
        return {
            "x": res,
            "y": jax.tree.map(jnp.copy, res) if self.error_feedback else (),
            "key": jax.random.PRNGKey(self.seed),
        }

    def _combine(self, tree: PyTree, q: PyTree) -> PyTree:
        diff = tree_sub(self.base_gossip(q), q)
        if self.gamma == 1.0:
            return tree_add(tree, diff)
        return jax.tree.map(lambda t, d: t + self.gamma * d, tree, diff)

    def __call__(
        self, tree: PyTree, residual: PyTree, key
    ) -> Tuple[PyTree, PyTree]:
        m = tree_add(tree, residual) if self.error_feedback else tree
        q = self.compressor.compress_tree(m, key)
        mixed = self._combine(tree, q)
        new_residual = tree_sub(m, q) if self.error_feedback else residual
        return mixed, new_residual

    def stateless(self, tree: PyTree) -> PyTree:
        """Keyless, residual-free form (installed as ``MixingOps.gossip``).

        Without a PRNG key, stochastic quantizers fall back to deterministic
        round-to-nearest here — lower per-round error but biased, and no
        error feedback.  Only PISCO's round function (which threads
        ``state.ef``) gets the stochastic/EF semantics a spec like 'q8'
        advertises; baseline algorithms run this form.
        """
        q = self.compressor.compress_tree(tree, key=None)
        return self._combine(tree, q)


def compress_mixing(
    base: MixingOps,
    compressor: Compressor,
    *,
    error_feedback: bool = True,
    seed: int = 0,
    gamma: Optional[float] = None,
) -> MixingOps:
    """Attach a compressor to any mixing operator (edge-list or collective).

    ``gossip`` becomes the stateless mean-preserving compressed form;
    PISCO's round function additionally picks up the stateful error-feedback
    path via the ``compression`` spec.  ``global_avg`` (the server round)
    stays full precision — the paper's emphasis is that server rounds set the
    consensus floor, so the expensive link gets the exact average.

    ``gamma=None`` auto-selects the consensus step: 1.0 for (near-)unbiased
    quantizers, 0.5 for contractive sparsifiers (top-k), which diverge
    undamped under aggressive local steps.
    """
    if isinstance(compressor, IdentityCompressor):
        return base
    if gamma is None:
        gamma = 0.5 if isinstance(compressor, TopKCompressor) else 1.0
    cg = CompressedGossip(
        base_gossip=base.gossip,
        compressor=compressor,
        error_feedback=error_feedback,
        seed=seed,
        gamma=gamma,
    )
    return dataclasses.replace(
        base,
        gossip=cg.stateless,
        name=f"{base.name}/{compressor.name}" + ("+ef" if error_feedback else ""),
        compression=cg,
    )


# ---------------------------------------------------------------------------
# Byte-level communication pricing
# ---------------------------------------------------------------------------


def _per_agent_leaf_sizes(template: PyTree, n_agents: int):
    for leaf in jax.tree.leaves(template):
        assert leaf.shape[0] == n_agents, (
            f"leaf {leaf.shape} is not agent-stacked over {n_agents} agents"
        )
        yield int(leaf.size) // n_agents, leaf.dtype.itemsize * 8


def message_bytes(
    compressor: Optional[Compressor], template: PyTree, n_agents: int
) -> int:
    """Bytes ONE agent ships per message for the agent-stacked ``template``."""
    comp = compressor or IdentityCompressor()
    bits = sum(
        comp.wire_bits(n, itemsize)
        for n, itemsize in _per_agent_leaf_sizes(template, n_agents)
    )
    return -(-bits // 8)


def _directed_gossip_messages(mixing: MixingOps) -> int:
    """Directed neighbor messages per gossip mix, network-wide: the explicit
    ``gossip_messages`` field when the mixer sets one (collective shift
    mixers, whose ``gossip_edges`` counts per-agent shifts), else one message
    per direction over each undirected edge."""
    if mixing.gossip_messages is not None:
        return mixing.gossip_messages
    return 2 * mixing.gossip_edges


def make_byte_model(
    mixing: MixingOps,
    template: PyTree,
    n_agents: int,
    *,
    mixes_per_round: int = 2,
    server_payloads: Optional[int] = None,
) -> RoundByteModel:
    """Closed-form network-wide bytes per round (Fig.-4 bits-on-x-axis).

    * gossip round: ``mixes_per_round`` mixes, each moving one *compressed*
      message per directed edge;
    * server round: ``server_payloads`` payloads per direction (defaults to
      ``mixes_per_round`` — gradient-tracking methods ship both streams), each
      an upload + a broadcast download per agent, *full precision*.
    """
    comp = mixing.compression.compressor if mixing.compression is not None else None
    if server_payloads is None:
        server_payloads = mixes_per_round
    gossip_msg = message_bytes(comp, template, n_agents)
    server_msg = message_bytes(None, template, n_agents)
    return RoundByteModel(
        gossip_round_bytes=mixes_per_round
        * _directed_gossip_messages(mixing)
        * gossip_msg,
        server_round_bytes=server_payloads * 2 * n_agents * server_msg,
        gossip_message_bytes=gossip_msg,
        server_message_bytes=server_msg,
        mixes_per_round=mixes_per_round,
        server_payloads=server_payloads,
    )
