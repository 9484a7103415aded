"""The one mesh constructor for the whole repo.

Two callers used to build meshes their own way: ``launch/mesh.py``
(``make_mesh_for`` over an explicit ``(shape, axes)`` for the
training/dry-run stack) and ad-hoc ``jax.sharding.Mesh(...)`` calls in
the distributed tests and examples. :func:`make_mesh` unifies them: one
function, importable without touching jax device state (a FUNCTION, not
a module constant -- dry-runs set ``XLA_FLAGS`` before any jax init),
used by the sharded serving engine, the launch stack, examples, and
benchmarks alike. The old names (``launch.mesh.make_mesh_for``) remain
as aliases.

The sharded :class:`~repro.serving.stream.StreamEngine` path wants the
simplest form: ``make_mesh()`` -- every local device on one ``("data",)``
axis, the axis the engine partitions its batch-slot dimension over (see
:func:`repro.distributed.sharding.slot_pspec`).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh

__all__ = ["make_mesh", "slot_axis"]


def make_mesh(shape: Union[None, int, Sequence[int]] = None,
              axes: Optional[Sequence[str]] = None, *,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a device mesh; the unified entrypoint.

    Forms (all over the first ``prod(shape)`` of ``devices``, default
    ``jax.devices()``):

      * ``make_mesh()`` -- every local device on one ``("data",)`` axis:
        the sharded-serving default (slot axis == data axis).
      * ``make_mesh(4)`` / ``make_mesh((4,))`` -- the first 4 devices on
        ``("data",)``.
      * ``make_mesh((2, 16, 16), ("pod", "data", "model"))`` -- the
        explicit launch-stack form (``launch.mesh.make_mesh_for`` is an
        alias of exactly this).

    ``axes`` defaults to ``("data",)`` for 1-D shapes and is required
    otherwise.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if shape is None:
        shape = (len(devices),)
    elif isinstance(shape, int):
        shape = (shape,)
    else:
        shape = tuple(int(s) for s in shape)
    if axes is None:
        if len(shape) != 1:
            raise ValueError(
                f"axes required for a {len(shape)}-D mesh shape {shape}; "
                f"only 1-D shapes default to ('data',)")
        axes = ("data",)
    axes = tuple(axes)
    if len(axes) != len(shape):
        raise ValueError(f"mesh shape {shape} and axes {axes} disagree")
    n = math.prod(shape)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} (or more) "
            f"before any jax import")
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices[:n])


def slot_axis(mesh: Mesh) -> str:
    """The mesh axis the serving engines shard their slot dimension
    over: ``"data"`` when the mesh has one (the launch-stack convention
    -- batch over data), else the mesh's first axis."""
    names: Tuple[str, ...] = tuple(mesh.axis_names)
    return "data" if "data" in names else names[0]
