"""Production meshes (DESIGN.md §4).

Defined as functions — importing this module never touches jax device
state. The dry-run sets XLA_FLAGS before any jax import to fabricate the
512 host devices (launch/dryrun.py lines 1-2)."""
from __future__ import annotations

import jax

from repro.utils.mesh import make_mesh_auto


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever devices exist right now (tests/examples on CPU)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh_auto((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The batch axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_devices(mesh) -> int:
    out = 1
    for v in mesh.shape.values():
        out *= v
    return out


__all__ = ["make_production_mesh", "make_local_mesh", "dp_axes", "n_devices"]
