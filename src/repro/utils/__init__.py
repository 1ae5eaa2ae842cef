from repro.utils.pytree import (
    tree_stack,
    tree_unstack,
    tree_zeros_like,
    tree_add,
    tree_sub,
    tree_scale,
    tree_axpy,
    tree_dot,
    tree_sq_norm,
    tree_agent_mean,
    tree_size,
    tree_bytes,
)

__all__ = [
    "tree_stack",
    "tree_unstack",
    "tree_zeros_like",
    "tree_add",
    "tree_sub",
    "tree_scale",
    "tree_axpy",
    "tree_dot",
    "tree_sq_norm",
    "tree_agent_mean",
    "tree_size",
    "tree_bytes",
]
