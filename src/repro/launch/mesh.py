"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count=512`` before first jax init; smoke
tests and benches see the 1 real CPU device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_mesh", "make_worker_mesh",
           "MeshAxes", "mesh_axes_of"]


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small CPU meshes)."""
    return _make_mesh(shape, axes)


def make_worker_mesh(n_devices: Optional[int] = None,
                     axis_name: str = "worker"):
    """1-D ``(worker,)`` mesh for the mesh-sharded FSI fleet backend
    (``pallas-bsr-sharded``): one mesh axis carrying the simulated-Lambda
    dimension, over the first ``n_devices`` devices (all by default).  Tests get >1 CPU
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set
    before the first jax init."""
    devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    return _make_mesh((len(devices),), (axis_name,), devices=devices)


class MeshAxes:
    """Resolved axis names for a mesh: which axes carry data vs model.

    ``as_pure_dp()`` reinterprets the whole mesh as data-parallel (the ZeRO
    strategy): every axis carries batch, no TP axis.
    """

    def __init__(self, mesh):
        names = mesh.axis_names
        self.model: Optional[str] = "model" if "model" in names else None
        dp = tuple(n for n in names if n in ("pod", "data"))
        self.dp: Tuple[str, ...] = dp
        self.mesh = mesh

    def as_pure_dp(self) -> "MeshAxes":
        out = MeshAxes(self.mesh)
        out.dp = tuple(self.mesh.axis_names)
        out.model = None
        return out

    def axis_size(self, name) -> int:
        if name is None:
            return 1
        if isinstance(name, tuple):
            return int(np.prod([self.axis_size(n) for n in name]))
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        return self.axis_size(self.dp) if self.dp else 1

    @property
    def model_size(self) -> int:
        return self.axis_size(self.model)


def mesh_axes_of(mesh) -> MeshAxes:
    return MeshAxes(mesh)
