"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — smoke tests see 1 CPU device; only
dryrun.py forces 512 host devices via XLA_FLAGS before any jax import.

Topology (TPU v5e, DESIGN.md "Distribution design"):
  single-pod: (16, 16)    -> ("data", "model")     256 chips
  multi-pod:  (2, 16, 16) -> ("pod", "data", "model")  512 chips

"model" is the innermost axis (contiguous chips -> fastest ICI ring for
the per-layer TP collectives); "pod" extends data parallelism across the
DCN boundary — exactly one gradient reduction crosses it per step.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def mesh_axis_kwargs(n_axes: int) -> dict:
    """``axis_types=`` kwargs for :func:`jax.make_mesh`: every axis Auto
    (the compiler partitions; no explicit-sharding types)."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **mesh_axis_kwargs(len(axes)))


def make_host_mesh(model: int = 1) -> Mesh:
    """Whatever this host offers (tests/examples): (n_dev/model, model)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         **mesh_axis_kwargs(2))


def make_tenant_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over local devices with a ``"tenants"`` axis — the seam
    the fleet's sharded chain dispatch (:func:`repro.core.annealing.
    fleet_chains`) splits its tenant blocks over.  ``n_devices`` limits
    the mesh to the first n devices (a single-device mesh is the parity
    fixture: shard_map over one device must be bit-identical to the
    direct dispatch)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(f"n_devices {n} out of range [1, {len(devs)}]")
    return jax.make_mesh((n,), ("tenants",), devices=devs[:n],
                         **mesh_axis_kwargs(1))


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size
