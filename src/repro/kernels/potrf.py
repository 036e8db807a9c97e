"""Pallas TPU kernel for the diagonal-block Cholesky ``A = L·Lᵀ`` (potrf).

This is the base-case engine of the packed blocked Cholesky
(``repro.solve.cholesky``): every diagonal block of the packed factor walk
is one ``bn × bn`` SPD tile, and under the batched-dispatch contract of the
stack (see the ``repro.kernels`` package docstring) a *stack* of diagonal
tiles — the per-level Shampoo stat batch — factors as **one** kernel
launch with the stack as the leading ("parallel") grid dimension.

In-kernel algorithm: the unblocked right-looking recurrence

    for j in 0..n-1:
        L[j,j]    = sqrt(A[j,j])
        L[j+1:,j] = A[j+1:,j] / L[j,j]
        A[j+1:,j+1:] -= L[j+1:,j]·L[j+1:,j]ᵀ

as ``n`` ``fori_loop`` steps of masked VPU column/rank-1 updates on the
VMEM-resident tile (column extraction and the diagonal pivot are masked
reductions — no dynamic slicing, so the same body compiles on Mosaic and
runs in interpret mode). The strictly-upper half of the output is zeroed:
the public contract is a *lower-triangular* factor tile, ready for packed
factor storage.

The sequential column loop is the nature of the factorization — ``potrf``
is O(n³/3) work on an O(n²) tile and sits on the recursion's critical path
only ``nb`` times per factorization (vs O(nb²) trsm/gemm panel work), so a
VPU-resident unblocked sweep is the right shape for ``bn ≤ 512`` tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct

__all__ = ["potrf_pallas"]


def _potrf_kernel(a_ref, l_ref, *, nn: int):
    a = a_ref[...].reshape(a_ref.shape[-2:]).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (nn, nn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nn, nn), 1)

    def body(j, a):
        # masked pivot/column extraction (no dynamic slicing on the tile)
        d = jnp.sqrt(jnp.sum(jnp.where((row == j) & (col == j), a, 0.0)))
        colj = jnp.sum(jnp.where(col == j, a, 0.0), axis=1)     # A[:, j]
        below = jnp.where(row[:, 0] > j, colj / d, 0.0)          # L[j+1:, j]
        newcol = below + jnp.where(row[:, 0] == j, d, 0.0)
        a = jnp.where(col == j, newcol[:, None], a)
        # rank-1 Schur update — `below` is zero at rows ≤ j, so the outer
        # product touches exactly the trailing submatrix
        return a - below[:, None] * below[None, :]

    a = jax.lax.fori_loop(0, nn, body, a)
    a = jnp.where(row >= col, a, 0.0)  # lower-triangular factor contract
    l_ref[...] = a.astype(l_ref.dtype).reshape(l_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def potrf_pallas(
    a: jax.Array,
    *,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Lower Cholesky factor of SPD tile(s) ``a: (n, n)`` or ``(B, n, n)``.

    A leading batch dim becomes the leading grid dimension — one launch for
    the whole stack (the ``repro.kernels`` batched-grid contract). The
    strict upper triangle of each output tile is zero.
    """
    if a.ndim not in (2, 3) or a.shape[-1] != a.shape[-2]:
        raise ValueError(f"potrf expects (n, n) or (B, n, n) SPD input, got {a.shape}")
    batched = a.ndim == 3
    nn = a.shape[-1]
    lead = (1,) if batched else ()
    batch_dims = a.shape[:-2]
    grid = batch_dims + (1,)
    _pre = lambda idx: idx[:-1]  # () unbatched, (b,) batched

    return pl.pallas_call(
        functools.partial(_potrf_kernel, nn=nn),
        grid=grid,
        in_specs=[pl.BlockSpec(lead + (nn, nn), lambda *idx: _pre(idx) + (0, 0))],
        out_specs=pl.BlockSpec(lead + (nn, nn), lambda *idx: _pre(idx) + (0, 0)),
        out_shape=out_struct(batch_dims + (nn, nn), out_dtype, a),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(batch_dims) + ("arbitrary",),
        ),
        interpret=interpret,
        name="potrf",
    )(a)
