"""Parameter-tree helpers shared by all model families.

Models are pure-JAX pytrees (nested dicts of arrays).  Every parameter
is created through ``param(...)`` which records its *logical axes*
(names like "embed", "mlp", "heads", "vocab").  ``repro.dist.sharding``
maps logical axes to mesh axes; models never mention mesh axes.

``init`` functions build a tree whose leaves are ``{"v": array,
"axes": (...)}`` markers; ``unzip`` splits that into (params, axes)
trees with identical structure.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Leaf = Dict[str, Any]

# The precision every forward pass computes in (matmuls, activations,
# the serving cache); parameters are kept in float32 and cast to it.
COMPUTE_DTYPE = jnp.bfloat16


def param(key, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
          dtype=jnp.float32, scale: Optional[float] = None,
          init: str = "normal") -> Leaf:
    """One parameter leaf with logical-axis metadata."""
    assert len(shape) == len(axes), (shape, axes)
    if init == "zeros":
        v = jnp.zeros(shape, dtype)
    elif init == "ones":
        v = jnp.ones(shape, dtype)
    else:
        if scale is None:
            # fan-in scaled normal (truncation unnecessary for smoke scale)
            fan_in = shape[0] if len(shape) == 1 else int(
                math.prod(shape[:-1]))
            scale = 1.0 / math.sqrt(max(fan_in, 1))
        v = scale * jax.random.normal(key, shape, dtype)
    return {"v": v, "axes": axes}


def is_leaf_marker(x: Any) -> bool:
    return isinstance(x, dict) and set(x.keys()) == {"v", "axes"}


def unzip(tree: Any) -> Tuple[Any, Any]:
    """Split a marker tree into (values, axes) trees."""
    values = jax.tree.map(lambda l: l["v"], tree, is_leaf=is_leaf_marker)
    axes = jax.tree.map(lambda l: l["axes"], tree, is_leaf=is_leaf_marker)
    return values, axes


def split_key(key, n: int):
    return list(jax.random.split(key, n))


def stack_inits(init_fn, key, n: int) -> Any:
    """Stack ``n`` independent inits of one layer along a leading axis.

    ``init_fn(key) -> marker tree``.  Uses vmap so tracing cost is O(1)
    in ``n`` (important: deepseek-67b has 95 layers and init is only
    ever *traced* for the dry-run via eval_shape).  The leading stacked
    axis gets logical axis ``None`` (layers are never sharded; they are
    the scan dimension).
    """
    keys = jax.random.split(key, n)

    def values_only(k):
        t = init_fn(k)
        return jax.tree.map(lambda m: m["v"], t, is_leaf=is_leaf_marker)

    vals = jax.vmap(values_only)(keys)
    proto = init_fn(keys[0])
    flat_vals, _ = jax.tree.flatten(vals)
    flat_proto, treedef = jax.tree.flatten(proto, is_leaf=is_leaf_marker)
    markers = [{"v": v, "axes": (None,) + tuple(m["axes"])}
               for v, m in zip(flat_vals, flat_proto)]
    return jax.tree.unflatten(treedef, markers)


def cast(tree: Any, dtype) -> Any:
    """Cast float leaves (compute precision policy: bf16 matmuls)."""
    def _c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(_c, tree)


class Sharder:
    """Activation-constraint hook threaded through model code.

    ``ac(x, logical_axes)`` applies ``with_sharding_constraint`` when a
    mesh is active; the default instance is the identity so model code
    runs unsharded (smoke tests) without any mesh.
    """

    def ac(self, x, axes: Tuple[Optional[str], ...]):
        return x

    # logical->mesh queries models may use for layout decisions
    def axis_size(self, logical: str) -> int:
        return 1


IDENTITY_SHARDER = Sharder()
