"""Mesh construction over local or distributed TPU devices."""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh, e.g. ``make_mesh({"dp": 2, "tp": 4})``.

    Axis sizes must multiply to the device count; ``-1`` for one axis means
    "use the remaining devices" (like a reshape wildcard).
    """
    devs = np.array(devices if devices is not None else jax.devices())
    sizes = list(axes.values())
    n_unknown = sum(1 for s in sizes if s == -1)
    if n_unknown > 1:
        raise ValueError("at most one axis size may be -1")
    if n_unknown == 1:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        if devs.size % known:
            raise ValueError(f"{devs.size} devices not divisible by {known}")
        sizes = [s if s != -1 else devs.size // known for s in sizes]
    if int(np.prod(sizes)) != devs.size:
        raise ValueError(f"mesh {dict(zip(axes, sizes))} needs "
                         f"{int(np.prod(sizes))} devices, have {devs.size}")
    return Mesh(devs.reshape(sizes), tuple(axes.keys()))


def replicate_on_mesh(tree, mesh: Mesh):
    """Commit every leaf to ``mesh``, replicated — the placement a dp step's
    outputs have, so its first call sees the same argument types as every
    later one (an argument's type carries its mesh; a fresh, unplaced init
    would make the second call trace again)."""
    return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


def default_mesh(axis: str = "dp") -> Optional[Mesh]:
    """All local devices on one data-parallel axis; None on a single device
    (plain jit is faster than a 1-device mesh)."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    return Mesh(np.array(devs), (axis,))


def mesh_axis_size(mesh: Optional[Mesh], axis: str) -> int:
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def parse_mesh_shape(s: str) -> Dict[str, int]:
    """Parse a mesh-shape string Param like ``"dp=2,tp=4"`` into axis sizes.

    Accepted axes: dp, tp, fsdp, sp, pp, ep. One axis may be ``-1``
    (remaining devices, like :func:`make_mesh`). This is the estimator-facing
    config format — a plain string so it persists like every reference Param.
    """
    known = ("dp", "tp", "fsdp", "sp", "pp", "ep")
    axes: Dict[str, int] = {}
    for part in (p.strip() for p in s.split(",") if p.strip()):
        if "=" not in part:
            raise ValueError(
                f"meshShape entry {part!r} is not 'axis=size' (got {s!r})")
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in known:
            raise ValueError(
                f"unknown mesh axis {name!r} in meshShape {s!r}; "
                f"known axes: {', '.join(known)}")
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r} in {s!r}")
        try:
            axes[name] = int(size)
        except ValueError:
            raise ValueError(
                f"mesh axis size {size!r} for {name!r} is not an integer")
    if not axes:
        raise ValueError(f"empty meshShape {s!r}")
    return axes
