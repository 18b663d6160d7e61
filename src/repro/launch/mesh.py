"""Production mesh construction (function, not module-level constant, so
importing this module never touches jax device state)."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def _mesh(devices, axes):
    return Mesh(devices, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries
    only the gradient all-reduce (pure DP), matching the DCN hierarchy.
    Scaling to 1000+ nodes grows the pod axis.

    Uses the first prod(shape) devices so the 256-chip mesh can be built in a
    512-device dry-run process."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            f"sets this automatically)")
    return _mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh for tests / elastic restarts."""
    n = int(np.prod(shape))
    return _mesh(np.asarray(jax.devices()[:n]).reshape(tuple(shape)),
                 tuple(axes))
