"""Production mesh builders.

Single pod: 16 x 16 = 256 chips (v5e pod), axes ("data", "model").
Multi-pod: 2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data parallelism over the DCI with gradient compression
(optim/compress.py); "data" is FSDP/batch inside a pod over ICI; "model" is
tensor/expert parallel.

Functions, not module constants: importing this module never touches jax
device state (the dry-run forces 512 host devices before the backend starts).
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
            f"mesh {shape} needs {n} devices, found {len(devices)} — run "
            "under launch/dryrun.py (it forces 512 host devices) or on a pod")
    import numpy as np
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for CPU tests (run under --xla_force_host_platform_device_count)."""
    import numpy as np
    n = 1
    for s in shape:
        n *= s
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def batch_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
