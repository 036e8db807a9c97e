"""Output shapes shared by the Pallas kernels."""

from __future__ import annotations

import jax

__all__ = ["out_struct"]


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel output's ``ShapeDtypeStruct``, varying over every mesh axis
    one of ``operands`` varies over.

    Under ``jax.shard_map`` (which checks varying manual axes) a
    ``pallas_call`` must state how its output varies; outside one the set
    is empty.
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
