"""Production mesh definitions (TPU v5e pods).

Defined as functions, not module-level constants, so importing this module
never touches JAX device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Sequence[str]) -> jax.sharding.Mesh:
    """Auto-axis mesh over the first prod(shape) devices."""
    axes = tuple(axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape: Tuple[int, ...] = (1, 1), axes=("data", "model")):
    """A 1x1 mesh over the single CPU device (used by unit tests)."""
    return make_mesh(shape, axes)


def agent_axes_for(mesh: jax.sharding.Mesh, mode: str = "flat"):
    """Which mesh axes form the PISCO agent axis.

    flat:          all non-model axes (16 agents single pod / 32 multi-pod)
    hierarchical:  the 'pod' axis only (beyond-paper mode, DESIGN.md §6)
    """
    names = list(mesh.axis_names)
    if mode == "hierarchical":
        assert "pod" in names, "hierarchical mode needs a pod axis"
        return ("pod",)
    return tuple(n for n in names if n != "model")


def n_agents_for(mesh: jax.sharding.Mesh, mode: str = "flat") -> int:
    axes = agent_axes_for(mesh, mode)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
