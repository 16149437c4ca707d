"""Production mesh factory (TPU v5e pod target).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state — smoke tests and benchmarks see 1 CPU device;
only launch/dryrun.py (which sets XLA_FLAGS first) sees 512.

Mesh axes are Auto: the sharding rule tables (dist/sharding.py) place
arrays GSPMD-style and let the compiler propagate, which Explicit axes
(``jax.make_mesh``'s default) would refuse.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "mesh_info"]


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) (data, model) single pod; (2,16,16) (pod, data, model) for 2."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Development mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_info(mesh) -> dict:
    return {
        "shape": dict(mesh.shape),
        "n_devices": mesh.size,
        "axis_names": list(mesh.axis_names),
    }
