"""Production mesh definitions.

A function (not a module-level constant) so importing never touches jax
device state. Single-pod: (data=16, model=16) = 256 chips (TPU v5e pod);
multi-pod: (pod=2, data=16, model=16) = 512 chips.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """The one mesh constructor: every axis Auto. ``jax.make_mesh`` defaults
    to Explicit axes, under which ``with_sharding_constraint`` and the
    gather-indexed paths of the sharding rules (runtime/sharding.py) fail."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for unit tests (requires >= data*model local devices)."""
    return make_mesh((data, model), ("data", "model"))


def make_replica_meshes(dp: int, tp: int, devices=None):
    """One (1, tp) mesh per data-parallel replica over disjoint device slices.

    Data parallelism in the serving stack is *replica* parallelism
    (DESIGN.md §9): each ``DataParallelEngine`` replica owns an
    independent block pool sharded over its own ``model`` axis, so each
    replica gets its own Mesh rather than one global (dp, tp) mesh.
    Replica ``i`` spans ``devices[i*tp : (i+1)*tp]``.
    """
    import numpy as np

    if dp < 1 or tp < 1:
        raise ValueError(f"dp and tp must be >= 1, got dp={dp} tp={tp}")
    if devices is None:
        devices = jax.devices()
    need = dp * tp
    if len(devices) < need:
        raise ValueError(
            f"dp={dp} x tp={tp} needs {need} devices, only {len(devices)} visible"
        )
    return [
        jax.sharding.Mesh(
            np.asarray(devices[i * tp : (i + 1) * tp]).reshape(1, tp),
            ("data", "model"),
        )
        for i in range(dp)
    ]
