"""Production mesh builders.

Single pod: 16x16 = 256 chips ("data","model").
Multi-pod : 2x16x16 = 512 chips ("pod","data","model") — "pod" is the
inter-pod DCN-ish axis used for pure data parallelism + gradient allreduce.

Defined as FUNCTIONS so importing this module never touches jax device
state (dryrun.py sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the sharding rules of this package
    use `with_sharding_constraint` and gathers, which Explicit axes (the
    make_mesh default in recent JAX) reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the same axis names (CPU smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_mesh_for(n_devices: int, *, data_model_ratio: float = 1.0):
    """Elastic-scaling helper: best (data, model) factorization of n."""
    best = (n_devices, 1)
    for m in range(1, n_devices + 1):
        if n_devices % m:
            continue
        d = n_devices // m
        if abs(d / m - data_model_ratio) < abs(best[0] / best[1]
                                               - data_model_ratio):
            best = (d, m)
    return _auto_mesh(best, ("data", "model"))
