"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization, and tests keep their single default device.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_production_mesh",
    "make_mesh",
    "merged_axis",
    "split_axis",
    "SINGLE_POD",
    "MULTI_POD",
]

SINGLE_POD = (16, 16)          # 256 chips / pod
MULTI_POD = (2, 16, 16)        # 2 pods = 512 chips


def make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with Auto axis types (GSPMD propagation), not the
    Explicit default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def merged_axis(
    task_axis: str, row_axis: Optional[str] = None
) -> Union[str, Tuple[str, str]]:
    """The device pool the BFS reduce-scatter runs over.

    ``ata_bfs_dfs`` stages every device's partial tiles at their global
    tri positions and issues ONE ``psum_scatter`` over the task and row
    axes *merged into a single logical axis* — the tuple form jax
    collectives accept. Chunk order is task-major (the tuple's first
    axis is the slowest-varying), which is exactly the order
    ``bfs_dfs_assignment`` deals contiguous tri chunks in, so the
    scattered result is already in packed tri order.
    """
    return (task_axis, row_axis) if row_axis is not None else task_axis


def split_axis(
    mesh: Mesh, axis: str, sizes: Sequence[int], names: Sequence[str]
) -> Mesh:
    """Refactor one mesh axis into named subgroup axes, same device order.

    BFS levels assign Strassen/tri subproblems to *subgroups* of the task
    axis. The tri-direct schedule addresses subgroups logically (slot
    tables over ``axis_index``), but callers that want explicit subgroup
    collectives — or meshes shaped for a fixed interleaving — can reshape
    the task axis into ``names`` of ``sizes`` (row-major over the original
    axis, so ``(grp, sub)`` subgroup ``g`` holds the devices that owned the
    contiguous index range ``[g·sub_size, (g+1)·sub_size)``).
    """
    import math

    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh {mesh.axis_names}")
    if len(sizes) != len(names):
        raise ValueError("sizes and names must pair up")
    if math.prod(sizes) != mesh.shape[axis]:
        raise ValueError(
            f"prod(sizes)={math.prod(sizes)} != mesh.shape[{axis!r}]"
            f"={mesh.shape[axis]}"
        )
    new_shape, new_names = [], []
    for name in mesh.axis_names:
        if name == axis:
            new_shape.extend(sizes)
            new_names.extend(names)
        else:
            new_shape.append(mesh.shape[name])
            new_names.append(name)
    return Mesh(
        mesh.devices.reshape(tuple(new_shape)), tuple(new_names)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
