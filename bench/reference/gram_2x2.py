"""Plain reference of the row-sharded packed Gram (configuration
``gram-f32-2x2``), and the float64 host product it was checked against.

It imports nothing of the program. ``A`` lies row-sharded over one axis of
a mesh. Each chip sums ``AᵀA`` over its own rows in blocks of at most
:data:`ROWS` rows, each block one product at the stated precision
(:func:`bench.reference.gram.tn`: ``"highest"``, or ``"high"``, three bf16
passes written out); one ``psum`` adds the chips' partial Grams; the sum is
cut into the documented packed tiles (tile ``t = i(i+1)/2 + j``, ``j ≤ i``,
of the ``nb × nb`` grid of ``bn × bn`` tiles). The blocks are there because
one ``HIGHEST`` product over 131072 rows errs 1.89e-5 on a v5e, above the
three-pass control, where products over 32768 rows do not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bench.reference.gram import tile_rel_err, tn

__all__ = ["ROWS", "gram", "packed_tiles", "float64_tile", "tile_rel_err"]

ROWS = 32768


def gram(a, precision: str, rows: int = ROWS):
    """``AᵀA`` of one chip's rows, summed over blocks of ``rows`` rows."""
    parts = [tn(a[r:r + rows], a[r:r + rows], precision)
             for r in range(0, a.shape[0], rows)]
    return sum(parts[1:], parts[0])


def _tiles(c, bn: int):
    n = c.shape[-1]
    nb = -(-n // bn)
    c = jnp.pad(c, [(0, nb * bn - n)] * 2)
    grid = jnp.moveaxis(c.reshape(nb, bn, nb, bn), 1, 2)   # (nb, nb, bn, bn)
    i, j = np.tril_indices(nb)
    return grid[i, j]


def packed_tiles(a, bn: int, precision: str = "highest", mesh=None,
                 row_axis=None, rows: int = ROWS):
    """The packed lower tiles ``(T, bn, bn)`` of ``AᵀA``; with a ``mesh``,
    ``a`` is sharded ``P(row_axis, None)`` on it and the answer is
    replicated."""
    if mesh is None:
        return _tiles(gram(a, precision, rows), bn)
    c = jax.shard_map(
        lambda x: jax.lax.psum(gram(x, precision, rows), row_axis),
        mesh=mesh, in_specs=P(row_axis, None), out_specs=P())(a)
    return _tiles(c, bn)


def float64_tile(a, bn: int, i: int, j: int) -> np.ndarray:
    """Tile ``(i, j)`` of ``AᵀA``, in float64 on the host."""
    ai = np.asarray(a[:, i * bn:(i + 1) * bn], np.float64)
    aj = np.asarray(a[:, j * bn:(j + 1) * bn], np.float64)
    return ai.T @ aj
