"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_tp_mesh(tp: int, axis: str = "tp") -> Mesh:
    """1-axis tensor-parallel serving mesh over the first `tp` devices
    (sharding/plans.ServingPlan documents the axis contract).  On a CPU
    host, fan devices out first: ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` before any jax initialization."""
    import numpy as np
    devs = jax.devices()
    if tp < 1 or tp > len(devs):
        raise ValueError(
            f"tp={tp} needs {tp} devices but only {len(devs)} are "
            f"visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={max(tp, 1)}")
    return Mesh(np.asarray(devs[:tp]), (axis,))


def make_host_mesh(shape: Tuple[int, ...] = None,
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Small CPU mesh from whatever devices exist (tests/examples)."""
    n = jax.device_count()
    if shape is None:
        model = 1
        for m in (4, 2, 1):
            if n % m == 0:
                model = m
                break
        shape = (n // model, model)
    return _make_mesh(shape, axes)
