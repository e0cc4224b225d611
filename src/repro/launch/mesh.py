"""Production meshes.  Functions, not module constants: importing this
module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=(data,model) single pod; (2,16,16)=(pod,data,model) for two
    pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_mesh_for(devices: int, model_parallel: int = 1):
    """Generic helper for tests/examples on whatever devices exist."""
    assert devices % model_parallel == 0
    return jax.make_mesh(
        (devices // model_parallel, model_parallel),
        ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_stage_mesh(n_stages: int, devices=None):
    """A 1-D ('stage',) mesh for layer-pipelined execution.

    Takes the first ``n_stages`` of ``devices`` (default: all visible).
    """
    import numpy as np

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if len(devices) < n_stages:
        raise ValueError(
            f"pipeline needs {n_stages} devices, only {len(devices)} visible"
        )
    return jax.sharding.Mesh(np.array(devices[:n_stages]), ("stage",))
