"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  Single pod:
(16, 16) = 256 chips as ("data", "model"); multi-pod: (2, 16, 16) = 512
chips as ("pod", "data", "model").  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so both meshes can be built on the CPU host.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_production_mesh", "dp_axes", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(jax.devices())} "
            f"(dry-run must set xla_force_host_platform_device_count first)")
    # Auto axes: the model's ``logical_shard`` calls are layout hints for
    # the partitioner, which Explicit axes would turn into assertions
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel / FSDP axes of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def batch_axes(mesh):
    """PartitionSpec entry for the global-batch dimension."""
    axes = dp_axes(mesh)
    return axes if len(axes) > 1 else axes[0]
