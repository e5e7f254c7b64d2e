"""Production meshes.

Functions, not module-level constants — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever devices exist locally (tests / examples): 1D data mesh."""
    n = jax.device_count()
    return _make_mesh((n,), ("data",))


def make_serving_mesh(size: int | None = None) -> Mesh:
    """1D ``("model",)`` mesh for the tensor-parallel serving executor.

    ``size`` caps/chooses the device count (None = all local devices).
    Built over the FIRST ``size`` devices with a plain :class:`Mesh` —
    unlike ``jax.make_mesh`` this permits a strict subset of the host's
    devices, which the executor needs when the model's head count only
    divides over part of a forced multi-device CPU host.
    """
    import numpy as np

    devices = jax.devices()
    n = len(devices) if size is None else size
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"serving mesh size {n} out of range [1, {len(devices)}]"
        )
    return Mesh(np.asarray(devices[:n]), ("model",))


def describe_mesh(mesh: Mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
