"""Plain reference of the packed Gram, and the comparison that decides
``correct`` for the ``gram-*`` configurations.

It imports nothing of the program. The packed layout it reproduces is the
documented one: tile ``t = i(i+1)/2 + j`` (``j ≤ i``) of the ``nb × nb``
grid of ``bn × bn`` tiles holds ``C[i·bn:(i+1)·bn, j·bn:(j+1)·bn]`` of
``C = AᵀA``, zero-padded where ``nb·bn > n``.

``precision`` is ``"highest"`` (float32 products to float32 accuracy, six
bf16 passes on a TPU) or ``"high"``: three bf16 passes, written out as the
split ``x = hi + lo`` with ``hi, lo`` in bfloat16 and
``xᵀy ≈ hiᵀhi + hiᵀlo + loᵀhi`` accumulated in float32. That is what
``Precision.HIGH`` computes on the TPU's MXU, and written out it computes
the same on any backend, so the control fails on a CPU test as it does on
the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["tn", "packed_tiles", "tile_rel_err"]


def tn(x, y, precision: str):
    """``xᵀy`` over the second-to-last axis, in float32."""
    dims = (((x.ndim - 2,), (y.ndim - 2,)),
            (tuple(range(x.ndim - 2)), tuple(range(y.ndim - 2))))
    if precision == "highest":
        return jax.lax.dot_general(x, y, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    if precision == "high":
        def split(v):
            # hi: v rounded to bfloat16's 8 significant bits, by masking the
            # float32 bits; lo: the rest, rounded to bfloat16. (Written as
            # a bfloat16 round trip, v − f32(bf16(v)), XLA's TPU compiler
            # folds the round trip away and lo becomes 0: one pass.)
            bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
            hi = jax.lax.bitcast_convert_type(
                (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000), jnp.float32)
            return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)

        (xh, xl), (yh, yl) = split(x), split(y)
        dot = lambda p, q: jax.lax.dot_general(
            p, q, dims, preferred_element_type=jnp.float32)
        return dot(xh, yh) + (dot(xh, yl) + dot(xl, yh))
    raise ValueError(f"unknown precision {precision!r}")


def packed_tiles(a, bn: int, precision: str = "highest"):
    """The packed lower tiles ``(..., T, bn, bn)`` of ``AᵀA``."""
    n = a.shape[-1]
    c = tn(a, a, precision)
    nb = -(-n // bn)
    pad = nb * bn - n
    if pad:
        c = jnp.pad(c, [(0, 0)] * (c.ndim - 2) + [(0, pad), (0, pad)])
    lead = c.shape[:-2]
    grid = c.reshape(*lead, nb, bn, nb, bn)
    grid = jnp.moveaxis(grid, -3, -2)              # (..., nb, nb, bn, bn)
    i, j = np.tril_indices(nb)
    return grid[..., i, j, :, :]


def tile_rel_err(got, ref) -> jax.Array:
    """Largest ``‖got_t − ref_t‖_F / ‖ref_t‖_F`` over every tile ``t`` (and
    batch entry). A non-finite tile reads ``inf``."""
    d = jnp.sqrt(jnp.sum(jnp.square(got - ref), axis=(-2, -1)))
    r = jnp.sqrt(jnp.sum(jnp.square(ref), axis=(-2, -1)))
    err = d / jnp.where(r > 0, r, 1.0)
    err = jnp.where(jnp.isfinite(err), err, jnp.inf)
    return jnp.max(err)
