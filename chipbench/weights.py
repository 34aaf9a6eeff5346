"""Seeded weights, made on the device in one jitted call.

The program's own initializer is never called: the harness takes only
the shapes and dtypes of the program's parameter tree and fills every
leaf from ``--seed`` by the rule the reference family gives for it
(``leaf_init(path, shape)``).  The reference makes the same weights
from the same seed with the same function.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp


def path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def make(shapes: Any, key, leaf_init: Callable) -> Any:
    """``shapes``: a tree of ``ShapeDtypeStruct``.  Leaf ``i`` is drawn
    from ``fold_in(key, i)`` in the order of the flattened tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            kind, scale = leaf_init(path_names(path), s.shape)
            if kind == "ones":
                v = jnp.ones(s.shape, s.dtype)
            elif kind == "zeros":
                v = jnp.zeros(s.shape, s.dtype)
            else:
                v = (scale * jax.random.normal(jax.random.fold_in(key, i),
                                               s.shape, jnp.float32)
                     ).astype(s.dtype)
            leaves.append(v)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)


def flat_named(tree: Any) -> dict:
    """``{"a/b/c": leaf}`` in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(path_names(p)): v for p, v in flat}
