"""Mesh construction.  Functions only — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run
sees 512 placeholder devices)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: one 256-chip pod (16×16) or two pods (2×16×16).

    ``pod`` is the slow (DCN / inter-pod) axis; ``data`` and ``model`` are ICI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """A mesh over the first prod(shape) of ``devices`` (default: the
    visible devices; described, unattached devices compile but never run)."""
    assert len(shape) == len(axes)
    n = int(np.prod(shape))
    have = len(jax.devices() if devices is None else devices)
    if n > have:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices, have {have}; "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before any jax import")
    return _auto_mesh(tuple(shape), tuple(axes),
                      None if devices is None else list(devices)[:n])


def _auto_mesh(shape, axes, devices=None):
    # jax.make_mesh defaults to Explicit axes, which with_sharding_constraint
    # refuses; the sharding rules here constrain by name, so axes are Auto
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry data parallelism (pod is an outer DP axis by default)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
