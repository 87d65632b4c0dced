"""Production mesh construction (as a function: no import-time device state).

Single pod: 16x16 = 256 chips -> ("data", "model")
Multi-pod:  2x16x16 = 512 chips -> ("pod", "data", "model")
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False, devices=None) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py sets it)"
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:n], axis_types=(AxisType.Auto,) * len(shape)
    )


def make_smoke_mesh(*, multi_pod: bool = False, devices=None) -> jax.sharding.Mesh:
    """Tiny mesh for CI-scale dry-run smoke tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    return jax.make_mesh(
        shape, axes, devices=devices[:n], axis_types=(AxisType.Auto,) * len(shape)
    )
