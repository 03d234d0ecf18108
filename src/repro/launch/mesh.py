"""Production meshes.

Single pod: (data=16, model=16) — 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis is
pure data parallelism so the only inter-pod (DCN) traffic is the gradient
all-reduce, which MBS amortizes to once per mini-batch.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state.

Every mesh is built with Auto axes: the model's ``shard_hint`` constraints
and the GSPMD step rely on the compiler propagating shardings, which
``jax.make_mesh``'s default Explicit axes forbid.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

DATA_AXIS = "data"
MODEL_AXIS = "model"
POD_AXIS = "pod"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod else (DATA_AXIS, MODEL_AXIS)
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — used by tests."""
    if pod:
        return _auto_mesh((pod, data, model), (POD_AXIS, DATA_AXIS, MODEL_AXIS))
    return _auto_mesh((data, model), (DATA_AXIS, MODEL_AXIS))


def parse_mesh_spec(spec: str, device_count: int | None = None):
    """Parse a launcher ``--mesh`` axis spec ``"DATA:MODEL"`` (e.g.
    ``"2:4"``) into ``(data, model)``, validated against the visible
    device count — fail fast at argument-parsing time instead of deep
    inside ``jax.make_mesh``. ``device_count=None`` reads the real
    backend."""
    parts = spec.split(":")
    if len(parts) != 2:
        raise ValueError(
            f"mesh spec {spec!r} is not of the form DATA:MODEL (two "
            "integers, e.g. '2:4' for a 2-way data x 4-stage pipeline "
            "mesh)")
    try:
        data, model = (int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"mesh spec {spec!r} is not of the form DATA:MODEL (two "
            "integers, e.g. '2:4')") from None
    if data < 1 or model < 1:
        raise ValueError(f"mesh spec {spec!r}: axis sizes must be >= 1")
    n = jax.device_count() if device_count is None else device_count
    if data * model > n:
        raise ValueError(
            f"mesh spec {spec!r} needs {data * model} devices but only "
            f"{n} are visible")
    return data, model


def batch_axes(mesh) -> tuple:
    """Mesh axes the batch dimension is sharded over."""
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def data_parallel_size(mesh) -> int:
    """Number of data-parallel workers: the product of the batch axes
    ((pod, data) when the pod axis exists, else data). This is the factor
    the planner divides the global micro-batch by to get the per-device
    ``local_micro`` (engine Layer 6)."""
    dp = 1
    for a in batch_axes(mesh):
        dp *= axis_size(mesh, a)
    return dp
