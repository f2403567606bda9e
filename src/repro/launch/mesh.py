"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis composes
with 'data' for batch/FSDP sharding (DCN-connected in production, so only
gradient/FSDP traffic crosses pods — attention/MoE TP stays intra-pod).
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the sharded paths here
    place arrays with ``NamedSharding`` and let the compiler propagate, and
    ``jax.make_mesh``'s own default is ``Explicit``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """The composed batch/FSDP axes for this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def smoke_mesh(n: int | None = None, with_model: bool = False):
    """Host-device mesh for tests (requires xla_force_host_platform_device_count)."""
    n = n or len(jax.devices())
    if with_model and n >= 4:
        return make_mesh((n // 2, 2), ("data", "model"))
    return make_mesh((n,), ("data",))


def data_mesh(ndev: int | None = None):
    """1-D ('data',) mesh over all (or the first ``ndev``) devices — the axis
    the sharded RSKPCA fit/transform path shards rows over (DESIGN.md §5).
    Works identically on a single device, so ``fit(..., mesh=data_mesh())``
    is always safe."""
    devices = jax.devices()
    ndev = ndev or len(devices)
    return make_mesh((ndev,), ("data",))
