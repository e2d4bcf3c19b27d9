"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — only `dryrun.py` forces the
512-device host platform, and only before its first jax import.

Topology (TPU v5e target):
  single pod : (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips
The "pod" axis is pure data parallelism — the only cross-pod traffic is
the once-per-step gradient all-reduce (DCN-friendly); "model" carries
TP/EP/SP collectives on intra-pod ICI.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return _make_mesh((data, model), ("data", "model"))


def mesh_devices(mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def validate_mesh(mesh) -> None:
    names = mesh.axis_names
    assert "data" in names and "model" in names, names
