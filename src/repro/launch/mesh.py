"""Production mesh construction.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before any jax import; tests and benchmarks see the 1 real CPU device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1, data: int = 1):
    """Small (data, model) mesh for tests / benchmarks / local serving.

    Uses the first ``data*model`` local devices — a 2×2 mesh on an
    8-device host is fine (the rest idle).  Asking for more devices than
    exist raises a ValueError naming both counts, so a bad ``--mesh``
    flag fails at startup instead of deep inside jax.
    """
    if model < 1 or data < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data} "
                         f"model={model}")
    devices = jax.devices()
    need, n = model * data, len(devices)
    if need > n:
        raise ValueError(
            f"mesh data={data} x model={model} needs {need} devices but "
            f"only {n} are available (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N to fake "
            f"more on CPU)")
    # Auto axes: the engine places arrays with explicit NamedShardings and
    # lets the compiler propagate the rest (jax.make_mesh defaults to
    # Explicit axes, under which the vocab-sharded embedding gather raises)
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=devices[:need],
                         axis_types=(AxisType.Auto,) * 2)


def mesh_info(mesh) -> dict:
    """Axis sizes plus the derived DP / TP degrees.  Meshes without a
    "pod" axis (every local mesh) get pod=1 folded into ``dp`` — callers
    should read ``dp``/``tp`` instead of poking at raw axis names."""
    axes = dict(mesh.shape)
    return {"axes": axes,
            "n_devices": int(np.prod(list(axes.values()))),
            "dp": int(axes.get("pod", 1)) * int(axes.get("data", 1)),
            "tp": int(axes.get("model", 1))}
