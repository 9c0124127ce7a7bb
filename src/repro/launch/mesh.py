"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module does not touch jax device state — smoke tests see 1 device; only
``dryrun.py`` forces 512 host devices.

Axis semantics (DESIGN.md §5):
  * "pod"   — cross-pod data parallelism (DCN; gradient all-reduce only)
  * "data"  — in-pod data parallel + FSDP storage axis
  * "model" — tensor/expert parallel (ICI)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Mesh over however many (CPU) devices exist — used by unit tests."""
    return _make_mesh((data, model), ("data", "model"))


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size
