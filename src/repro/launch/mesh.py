"""Production meshes (contract §MULTI-POD DRY-RUN).

A function, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py sets
XLA_FLAGS for 512 placeholder devices before any jax import).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "dryrun.py must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device subprocess tests."""
    import numpy as np
    n = 1
    for s in shape:
        n *= s
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)
