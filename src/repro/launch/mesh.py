"""Production mesh builders.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (per the dry-run contract).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; (2,16,16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (tests/examples)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_tp_mesh(tp: int):
    """1-D tensor-parallel serving mesh over the first ``tp`` devices.

    The serving engine shards weights/KV-heads over this single "model"
    axis (DESIGN.md §15); data parallelism is the scheduler's job
    (separate engine replicas), so the serving mesh never carries a
    "data" axis.  Works on any backend with >= tp devices, including
    the CPU backend under ``--xla_force_host_platform_device_count``.
    """
    assert tp >= 1, tp
    if tp > jax.device_count():
        raise ValueError(
            f"tensor-parallel mesh needs {tp} devices but the backend "
            f"has {jax.device_count()} (CPU tests: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before importing "
            "jax)")
    return jax.make_mesh((tp,), ("model",), axis_types=(AxisType.Auto,))
