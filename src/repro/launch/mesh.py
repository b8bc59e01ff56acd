"""Device meshes — the one place the library builds a ``jax.Mesh``.

Single pod: 16×16 = 256 chips (v5e pod), axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the
leading "pod" axis crosses DCN and is used for data parallelism (plus the
compressed gradient reduction in repro.distributed.compression).

Every mesh has Auto axes: the gspmd engine and the LM stack place data
with ``with_sharding_constraint`` and leave the collectives to the XLA
partitioner, which Explicit axes (``jax.make_mesh``'s default since JAX
0.7) refuse.  The shard_map engines are indifferent to the axis type.

The constructors are functions (not module constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Mesh of ``shape`` over ``devices`` (default: all) with Auto axes.
    Elastic restarts pass an explicit device list to rebuild a smaller
    mesh after excluding failed hosts."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def solver_mesh(devices=None):
    """2-D process grid for the CUPLSS solver layer (paper's logical mesh):
    squarest (p, q) factorization of the device count."""
    devices = jax.devices() if devices is None else devices
    n = len(devices)
    p = int(n ** 0.5)
    while n % p:
        p -= 1
    return make_mesh((p, n // p), ("data", "model"), devices=devices)
