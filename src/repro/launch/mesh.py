"""Production meshes.

Single pod: 16×16 = 256 chips (TPU v5e pod), axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — `pod` is an
outer data-parallel axis (gradient all-reduce over DCI/optical links; the
gradient-compression path in optim/grad_compress targets exactly this hop) or,
optionally, a pipeline axis (launch/pipeline.py).

Functions, not module constants: importing this module never touches jax
device state (required so smoke tests see 1 CPU device while the dry-run sees
512 placeholder devices via XLA_FLAGS).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "batch_axes_of"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many real devices exist (tests/examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes_of(mesh) -> tuple[str, ...]:
    """Mesh axes that carry the batch/chains dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
