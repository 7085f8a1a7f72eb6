"""Production meshes (TPU v5e: 256 chips/pod, 16×16 ICI torus).

``make_production_mesh`` is a FUNCTION (importing this module never touches jax
device state). Single-pod: (16, 16) = ("data", "model"). Multi-pod: (2, 16, 16) =
("pod", "data", "model") — the "pod" axis carries data parallelism over DCN plus
the (optionally compressed) cross-pod gradient reduction.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: GSPMD decides how intermediates are laid out
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2, pod: int = 0):
    """Small mesh over forced host devices (tests / examples)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def dp_size(mesh) -> int:
    out = mesh.shape["data"]
    if "pod" in mesh.shape:
        out *= mesh.shape["pod"]
    return out
