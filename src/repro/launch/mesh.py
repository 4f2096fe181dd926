"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — required because the dry-run must set
XLA_FLAGS before any JAX initialization.
"""
from __future__ import annotations

import jax

#: ambient-mesh context manager (``with use_mesh(mesh): ...``)
use_mesh = jax.set_mesh


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: `data` (DP/FSDP), `model` (TP/EP); `pod` is the slow inter-pod
    axis (DCN) used for data parallelism (and optionally pipeline stages,
    see launch/pipeline.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return _make_mesh(shape, axes, devices=jax.devices()[:n])


def make_host_mesh():
    """Single-device mesh for CPU tests (same axis names as production)."""
    return _make_mesh((1, 1), ("data", "model"), devices=None)
