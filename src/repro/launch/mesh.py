"""Production mesh definitions.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state -- required because the dry-run forces 512
host devices while tests and benches must see the single real device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2x16x16 =
    512 chips (pod, data, model); the pod axis carries pure data parallelism
    over DCN, proving the cross-pod sharding lowers."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_fleet_mesh(n_shards: int | None = None):
    """1-D mesh over host chips for sharding the fleet simulator's device
    axis (``repro.core.fleetsim``): fleets past ~1e6 simulated devices split
    their lanes across the mesh instead of living in one chip's memory.
    Defaults to every available device; on a single-chip host this is a
    ``(1,)`` mesh, which exercises the identical sharded code path."""
    n = len(jax.devices()) if n_shards is None else n_shards
    return jax.make_mesh((n,), ("devices",), axis_types=(AxisType.Auto,))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_chips(mesh) -> int:
    return mesh.devices.size
