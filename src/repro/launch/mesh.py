"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the `pod` axis
carries only data parallelism (gradient all-reduce over DCI), keeping all
TP collectives inside a pod's ICI domain.

A FUNCTION, not a module constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax use).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    """Auto-sharded axes: the partitioner propagates shardings through
    gathers and scans (Explicit axes reject the embedding lookup's
    inferred spec)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)}; "
            f"run under launch/dryrun.py (XLA_FLAGS host device count) "
            f"or on a real pod slice")
    return _mesh(shape, axes, devices[:need])


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over available devices for tests."""
    need = data * model
    devices = jax.devices()
    assert len(devices) >= need
    return _mesh((data, model), ("data", "model"), devices[:need])
