"""Production mesh construction (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.

- single-pod: (16, 16)   axes ("data", "model")   — 256 chips
- multi-pod:  (2, 16, 16) axes ("pod", "data", "model") — 512 chips,
  pure data parallelism across pods (gradient all-reduce crosses DCI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def mesh_axis_kwargs(n_axes: int) -> dict:
    """``axis_types=`` kwargs for ``jax.make_mesh``: every axis Auto."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with Auto axis types (GSPMD sharding propagation
    on every axis)."""
    return jax.make_mesh(shape, axes, **mesh_axis_kwargs(len(axes)))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh_compat((data, model), ("data", "model"))


def mesh_devices(mesh) -> list:
    """Row-major device list of a mesh — position ``i`` here is fabric
    logical device ``i`` (the contract the elastic sharded-arena path
    uses to map ``ClusterView`` homes onto jax devices)."""
    import numpy as np
    return list(np.asarray(mesh.devices).reshape(-1))


def survivor_mesh(devices):
    """Mesh over an explicit surviving device list: ``(n, 1)`` with axes
    ``("data", "model")`` — model parallelism collapses on shrink (the
    survivor set need not tile the original model axis), data
    parallelism carries the remaining throughput. Re-grow rebuilds the
    original mesh shape via :func:`make_mesh_compat`."""
    import numpy as np
    from jax.sharding import Mesh
    devs = np.asarray(list(devices), dtype=object)
    return Mesh(devs.reshape(devs.size, 1), ("data", "model"))
