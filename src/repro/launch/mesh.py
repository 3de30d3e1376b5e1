"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — `pod` spans the
DCN link between pods; `data`/`model` span ICI within a pod.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_local_mesh(model_axis: int = 1, data_axis: int = 1):
    """Small mesh over whatever devices exist (tests/smokes)."""
    n = len(jax.devices())
    data_axis = max(1, min(data_axis, n // model_axis))
    return jax.make_mesh((data_axis, model_axis), ("data", "model"),
                         axis_types=(AUTO,) * 2)


def make_serve_mesh(data: int = 0, fleet: int = 1, devices=None):
    """Serving mesh, axes ``("data", "fleet")``.

    ``data`` spans the engine's decode-batch (cache slot) dimension —
    classic data parallelism over concurrent streams. ``fleet`` places a
    projection's output-channel tiles across devices (macro placement:
    each device holds a contiguous slice of every programmed µArray
    bank). ``data=0`` takes every device not consumed by ``fleet``.
    ``devices`` restricts the mesh to an explicit device list (e.g. the
    single-device parity mesh) — built through ``jax.sharding.Mesh``
    directly because ``jax.make_mesh`` always lays its mesh out over the
    global device set.
    """
    if devices is None:
        n = len(jax.devices())
        if data <= 0:
            data = max(1, n // fleet)
        return jax.make_mesh((data, fleet), ("data", "fleet"),
                             axis_types=(AUTO,) * 2)
    import numpy as np
    devs = np.asarray(devices, dtype=object)
    if data <= 0:
        data = max(1, devs.size // fleet)
    if devs.size != data * fleet:
        raise ValueError(
            f"{devs.size} devices do not fill a ({data}, {fleet}) mesh")
    return jax.sharding.Mesh(devs.reshape(data, fleet), ("data", "fleet"))
