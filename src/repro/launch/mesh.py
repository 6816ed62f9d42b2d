"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count locks on first backend init)."""
from __future__ import annotations

import math

import jax
import numpy as np


def _mesh(shape, axes):
    """Build a Mesh over the first prod(shape) devices.  Explicit device
    slicing (rather than jax.make_mesh) so the 512 host-platform placeholder
    devices the dry-run forces can carry a 256-chip single-pod mesh."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {dict(zip(axes, shape))} needs {n} devices, have {len(devices)}"
        )
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (16, 16) ("data", "model").
    Multi-pod: 2 pods x 256 chips as (2, 16, 16) ("pod", "data", "model") —
    the pod axis carries data parallelism (and optional gradient-compressed
    all-reduce, dist/compress.py) across the inter-pod DCN/ICI boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(n: int = 1, model: int = 1):
    """Small debugging mesh over host devices (tests use subprocesses with
    --xla_force_host_platform_device_count)."""
    return _mesh((n, model), ("data", "model"))


def host_mesh_from_spec(spec: str):
    """Parse a "DxM" CLI string (e.g. "2x2") into a (data, model) host mesh
    — the shared --mesh handling of launch/train.py and launch/serve.py."""
    parts = spec.lower().split("x")
    try:
        d, m = (int(v) for v in parts)
        if d < 1 or m < 1:
            raise ValueError
    except ValueError:
        raise ValueError(
            f'bad mesh spec {spec!r}: expected "DxM" (data x model), e.g. "2x2"'
        ) from None
    return make_host_mesh(d, m)
