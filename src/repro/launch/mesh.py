"""Mesh construction.  Functions, not module-level constants — importing
this module never touches jax device state."""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Production v5e meshes: one pod = 256 chips as (data=16, model=16);
    two pods = 512 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, found {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this automatically)")
    return _mesh(shape, axes, devices[:n])


def make_auto_mesh(model: int = 1):
    """All visible devices as one (data, model) mesh — the default for
    ``train.py --executor sharded``: the data axis (cohort sharding for the
    two-tier aggregation) takes every device the model axis doesn't."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"model={model} must be >= 1 and divide the device count {n}")
    return _mesh((n // model, model), ("data", "model"), jax.devices())


def make_debug_mesh(data: int = 1, model: int = 1, *, pod: int = 0):
    """Small mesh for smoke tests (uses however many devices exist)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"),
                     jax.devices()[:pod * data * model])
    return _mesh((data, model), ("data", "model"),
                 jax.devices()[:data * model])
