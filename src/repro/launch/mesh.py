"""Production mesh construction (multi-pod dry-run spec).

Defined as functions — importing this module never touches jax device
state, so smoke tests see 1 device while the dry-run (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import)
sees its 512 placeholder devices.
"""

from __future__ import annotations

import warnings

import jax


def abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A device-free ``AbstractMesh`` of ``shape`` over ``axes``."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(model: int = 1, *, devices=None):
    """A ``data × model`` mesh over however many devices exist.

    ``devices`` pins an explicit device subset (tests use this to build
    1/2/4-device meshes inside one forced-multi-device process); the
    default is every device the backend exposes.

    When the requested ``model`` axis does not divide the device count —
    the classic single-device-CI trip, ``jax.device_count() == 1`` with
    ``model > 1`` — this *falls back* to the largest model-axis size the
    devices do support and says so, instead of raising an opaque
    ``ValueError``.  Call sites therefore run unchanged on one device
    and only actually shard under the forced-multi-device lane.
    """
    import numpy as np
    from jax.sharding import Mesh

    if model < 1:
        raise ValueError(f"make_debug_mesh: model axis must be >= 1, "
                         f"got model={model}")
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if n % model:
        fallback = max(m for m in range(1, model + 1) if n % m == 0)
        warnings.warn(
            f"make_debug_mesh: {n} device(s) cannot host a model axis of "
            f"{model} (not a divisor); falling back to model={fallback}. "
            f"Set XLA_FLAGS=--xla_force_host_platform_device_count=<N> "
            f"before importing jax to debug real sharding.",
            RuntimeWarning, stacklevel=2)
        model = fallback
    return Mesh(np.asarray(devs).reshape(n // model, model),
                ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes for this mesh (pod folds into data)."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def axis_size(mesh, axes: tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size
