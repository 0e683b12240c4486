"""Production mesh builders.

Functions, not module constants, so importing this module never touches
jax device state (the dry-run forces 512 host devices before first init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} "
            "(dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import)"
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:n], axis_types=(AxisType.Auto,) * len(shape)
    )


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model_parallel
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         devices=jax.devices()[: data * model_parallel],
                         axis_types=(AxisType.Auto,) * 2)
