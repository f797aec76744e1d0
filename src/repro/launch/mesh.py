"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init and
smoke tests must keep seeing one device.

Single pod:  (16, 16)      axes (data, model)          — 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes (pod, data, model)     — 512 chips

Batch (and SD-KDE point rows) shard over (pod, data); tensor-parallel
weights over model.  All cross-pod traffic rides the slower inter-pod links
→ the ring schedules in distributed/ring.py and the gradient all-reduce are
laid out so per-pod reductions happen first (GSPMD emits hierarchical
all-reduces for the nested (pod, data) spec).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis marked ``AxisType.Auto``."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """The axes the global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_desc(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names) + (
        f" ({','.join(mesh.axis_names)})"
    )
