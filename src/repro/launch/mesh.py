"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because
smoke tests must see 1 CPU device while the dry-run forces 512
placeholder devices via XLA_FLAGS before any jax import.

Mesh layout (TPU v5e):
  single pod : (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

The "model" axis maps onto one torus dimension (TP + sequence-parallel
collectives stay on neighbor ICI links); "data" onto the other (FSDP
all-gather / gradient reduce-scatter); "pod" crosses the DCN (gradient
all-reduce of the pod-local reduce-scatter result — the dist-gem5
hierarchical layering).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """Arbitrary mesh (DSE sweeps / tests on few host devices)."""
    return _mk(shape, axes)


def single_device_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the production axis names (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


def describe(mesh: jax.sharding.Mesh) -> dict:
    return {"axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "devices": int(mesh.devices.size)}
