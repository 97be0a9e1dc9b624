"""Production mesh definitions (TPU v5e pods).

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


#: The chip the production meshes (and so the dry-runs) target, as
#: ``jax.Device.device_kind`` names it.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (CPU demos / tests)."""
    n = len(jax.devices())
    assert n % model_parallel == 0, (n, model_parallel)
    return jax.make_mesh((n // model_parallel, model_parallel), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_tenant_mesh(n_devices: int | None = None):
    """1-D mesh named 'tenants' for sharded DAEF fleets (core/fleet_sharded):
    K tenant models split K/D per device.  Defaults to every device."""
    from repro.core import fleet_sharded

    return fleet_sharded.tenant_mesh(n_devices)


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes of a mesh (('pod','data') when multi-pod)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
