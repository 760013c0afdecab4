"""Production mesh builders.

Importing this module never touches jax device state; meshes are built
inside functions only.  The dry-run sets XLA_FLAGS for 512 host devices
*before* importing anything (see dryrun.py); everything else sees the real
device count.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple, devices):
    """``jax.make_mesh`` with every axis Auto: GSPMD propagates shardings
    from the params and caches (the partition rules), as the model code and
    the ``shard_map``'d kernels expect.  ``make_mesh`` defaults to Explicit
    axes, which make every unannotated gather and sharding constraint an
    error."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — "
            f"run via launch/dryrun.py which forces host platform devices")
    return _auto_mesh(shape, axes, devices[:n])


def make_mesh(shape: tuple, axes: tuple):
    n = int(np.prod(shape))
    return _auto_mesh(shape, axes, jax.devices()[:n])


def parse_mesh(spec):
    """Mesh from a ``"DxM"`` / ``"PxDxM"`` string (or an int tuple): 2 dims
    map to ``(data, model)``, 3 to ``(pod, data, model)``.  The single spec
    parser every CLI entry point (serve / dryrun / shardcheck / benches)
    shares."""
    if isinstance(spec, str):
        shape = tuple(int(x) for x in spec.split("x"))
    else:
        shape = tuple(int(x) for x in spec)
    if len(shape) not in (2, 3) or any(s < 1 for s in shape):
        raise ValueError(f"mesh spec {spec!r} must be DxM or PxDxM with "
                         f"positive sizes")
    axes = (("pod", "data", "model") if len(shape) == 3
            else ("data", "model"))
    return make_mesh(shape, axes)


def make_mesh_auto(*, max_model: int = 4, devices=None):
    """Largest ``(data, model)`` mesh the available devices support.

    Unlike :func:`make_production_mesh` this never hard-fails on device
    count: it uses every device it finds, putting the largest power-of-two
    factor <= ``max_model`` on "model" (TP wants the fast intra-host links)
    and the rest on "data".  One device degenerates to
    :func:`single_device_mesh` — the no-op mesh every entry point accepts.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    model = 1
    while model * 2 <= max_model and n % (model * 2) == 0:
        model *= 2
    return _auto_mesh((n // model, model), ("data", "model"), devices)


def single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])
