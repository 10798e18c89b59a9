"""Device mesh construction: the parallelism substrate.

Replaces the reference's process-group choreography (§2.3-2.4 of SURVEY.md)
with jax meshes: a named-axis mesh is the single object every strategy (DP /
FSDP / TP / SP / EP / PP) hangs off. On TPU hardware,
``mesh_utils.create_device_mesh`` lays axes onto the ICI torus so the
innermost axes get the fastest links; on CPU test meshes we reshape directly.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

import jax
from jax.sharding import Mesh

# canonical axis order: outer (slow/DCN-ish) to inner (fast ICI); tp innermost
AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "ep", "tp")


def make_mesh(axes: Dict[str, int],
              devices: Optional[List] = None) -> Mesh:
    """Build a mesh with the given {axis: size}. Axes are laid out in
    AXIS_ORDER (unknown names go last in given order)."""
    names = sorted(
        axes.keys(),
        key=lambda n: AXIS_ORDER.index(n) if n in AXIS_ORDER else 99,
    )
    shape = tuple(axes[n] for n in names)
    n_dev = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    if n_dev > len(devices):
        raise ValueError(
            f"mesh {axes} needs {n_dev} devices, have {len(devices)}"
        )
    devices = devices[:n_dev]
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        arr = np.array(devices).reshape(shape)
    return Mesh(arr, names)


def cpu_mesh(axes: Dict[str, int]) -> Mesh:
    """Test mesh over the forced-host-device CPU backend."""
    return make_mesh(axes, devices=jax.devices("cpu"))


def local_tpu_mesh(axes: Optional[Dict[str, int]] = None) -> Mesh:
    """Mesh over this host's TPU chips (the host-process model: one process
    owns 4-8 chips). Raises where this process has no TPU backend; a CPU
    mesh is asked for by name (``cpu_mesh``)."""
    devices = jax.devices("tpu")
    if axes is None:
        axes = {"dp": len(devices)}
    return make_mesh(axes, devices=devices)
