"""Production meshes.

Defined as a FUNCTION (not a module-level constant) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def _make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated shardings)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(_AUTO,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) 'data' x 'model' single pod; (2,16,16) 'pod' x 'data' x 'model'
    across two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    devices = jax.devices()[:ndev]
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}; have {len(devices)}. "
            "The dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count=512 before importing jax."
        )
    return _make_mesh(shape, axes, devices)


def make_smoke_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for in-subprocess sharding tests (8 forced host devices)."""
    return _make_mesh(shape, axes, jax.devices()[: shape[0] * shape[1]])


def make_serving_mesh(tp: int, devices=None):
    """``('data', 'model')`` mesh for one tensor-parallel serving engine.

    ``devices`` (default ``jax.devices()[:tp]``) become the 'model' axis of a
    (1, tp) mesh; the 'data' axis is size 1 because replica-level parallelism
    is composed OUTSIDE the mesh by ``DataParallelEngine`` (each replica gets
    its own sub-mesh — 2D replica x tensor fleets without a global mesh).
    """
    devices = list(devices) if devices is not None else jax.devices()[:tp]
    if len(devices) < tp:
        raise RuntimeError(
            f"tensor_parallel={tp} needs {tp} devices; have {len(devices)}")
    return _make_mesh((1, tp), ("data", "model"), devices[:tp])
