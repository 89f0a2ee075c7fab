"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches jax
device state): single pod = 16x16 ("data","model"), multi-pod = 2x16x16
("pod","data","model"). Any pod count works (elastic): pass ``pods=N``.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def build_mesh(dev, axes) -> Mesh:
    """``Mesh`` with Auto axis types. ``dev``: ndarray of devices shaped like
    the mesh."""
    return Mesh(dev, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2):
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(devices_total: int, model_parallel: int = 16, pods: int = 1):
    """Elastic variant: build the best (pod, data, model) mesh for any device count."""
    per_pod = devices_total // pods
    model = min(model_parallel, per_pod)
    data = per_pod // model
    if pods > 1:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def _mesh(shape, axes) -> Mesh:
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "the dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count before importing jax")
    dev = np.asarray(devices[:n]).reshape(shape)
    return build_mesh(dev, axes)
