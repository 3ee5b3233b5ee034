"""Meshes.  Functions, not module-level constants — importing this module
never touches jax device state."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_local_mesh(tp: int = 1, devices: Sequence | None = None):
    """("data", "model") mesh over the local devices (``jax.devices()``
    unless ``devices`` is given): ``model = tp``, ``data = count // tp``.
    This is the mesh the launchers run on, one chip or a 2x2 host alike."""
    devs = list(jax.devices() if devices is None else devices)
    if tp < 1 or len(devs) % tp:
        raise ValueError(
            f"--tp {tp} must divide the {len(devs)} local devices")
    return jax.make_mesh((len(devs) // tp, tp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devs)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips):
    the dry-run and simulator target, not something a launcher can
    build on one host."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(data: int = 1, model: int = 1, stage: int = 0):
    """Tiny mesh for CPU tests; axes always present so all collective code
    paths run (psum over size-1 axes is the identity).  ``stage >= 1``
    inserts a "stage" axis between "data" and "model" — dp×stage×tp,
    the §15 pipeline smoke topology (extent 1 keeps the staged code path
    with a trivial pipeline: the bit-exact stage=1 reference).  The
    default 0 keeps the legacy two-axis mesh."""
    if stage >= 1:
        n = data * stage * model
        return jax.make_mesh(
            (data, stage, model), ("data", "stage", "model"),
            axis_types=(AxisType.Auto,) * 3,
            devices=jax.devices()[:n])
    n = data * model
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
        devices=jax.devices()[:n])


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
