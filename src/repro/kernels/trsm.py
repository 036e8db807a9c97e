"""Pallas TPU kernel for the triangular panel solve (trsm).

The second base-case engine of the packed solver layer
(``repro.solve``): given the lower-triangular diagonal factor tile ``L``
of one block column, solve

    X · Lᵀ = B      (``transpose=True``  — the factorization panel op:
                     ``L[i,j] = S[i,j]·L[j,j]⁻ᵀ`` of the blocked Cholesky)
    X · L  = B      (``transpose=False`` — the backward-substitution form:
                     ``Lᵀx = y  ⇔  xᵀ·L = yᵀ``)

for a row panel ``B``. Each row of ``X`` is independent, so the kernel
grid blocks the panel rows ("parallel") while the column recurrence runs
as ``n`` ``fori_loop`` steps of masked VPU updates inside the tile:

    X[:,j] = (B[:,j] − Σ_k X[:,k]·op(L)[k,j]) / L[j,j]

with ``j`` ascending for ``X·Lᵀ = B`` and descending for ``X·L = B``
(the factor row/column and the pivot are masked reductions — no dynamic
slicing, so one body serves Mosaic and interpret mode alike).

Batched: a leading stack dimension on BOTH operands (each panel entry has
its *own* factor tile, e.g. all block rows of all batch entries of a
Shampoo stat stack) becomes the leading grid dimension — one launch per
stack, per the package-wide batched-grid contract in ``repro.kernels``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shapes import out_struct

__all__ = ["trsm_pallas"]


def _trsm_kernel(l_ref, b_ref, x_ref, *, nn: int, transpose: bool):
    l = l_ref[...].reshape(l_ref.shape[-2:]).astype(jnp.float32)
    b = b_ref[...].reshape(b_ref.shape[-2:]).astype(jnp.float32)
    mm = b.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (nn, nn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nn, nn), 1)
    k1d = row[:, 0]                                            # (nn,)
    bcol_ids = jax.lax.broadcasted_iota(jnp.int32, (mm, nn), 1)

    def body(step, x):
        j = step if transpose else nn - 1 - step
        d = jnp.sum(jnp.where((row == j) & (col == j), l, 0.0))
        if transpose:
            # op(L)[k, j] = L[j, k], known entries k < j
            lvec = jnp.sum(jnp.where(row == j, l, 0.0), axis=0)
            lvec = jnp.where(k1d < j, lvec, 0.0)
        else:
            # op(L)[k, j] = L[k, j], known entries k > j
            lvec = jnp.sum(jnp.where(col == j, l, 0.0), axis=1)
            lvec = jnp.where(k1d > j, lvec, 0.0)
        acc = jnp.sum(x * lvec[None, :], axis=1)               # X·op(L)[:,j]
        bj = jnp.sum(jnp.where(bcol_ids == j, b, 0.0), axis=1)
        return jnp.where(bcol_ids == j, ((bj - acc) / d)[:, None], x)

    x = jax.lax.fori_loop(0, nn, body, jnp.zeros((mm, nn), jnp.float32))
    x_ref[...] = x.astype(x_ref.dtype).reshape(x_ref.shape)


def _pad_rows(x, mult):
    m = x.shape[-2]
    pm = (-m) % mult
    if pm:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, 0)])
    return x


@functools.partial(
    jax.jit, static_argnames=("transpose", "block_rows", "interpret", "out_dtype")
)
def trsm_pallas(
    l: jax.Array,
    b: jax.Array,
    *,
    transpose: bool = True,
    block_rows: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Solve ``X·Lᵀ = B`` (``transpose=True``) or ``X·L = B`` against the
    lower-triangular ``l: (n, n)`` or stacked ``(B, n, n)``, panel
    ``b: (m, n)`` or ``(B, m, n)``.

    The panel rows are blocked over a parallel grid dimension (rows are
    independent); a leading batch dim becomes the leading grid dimension —
    one launch for the whole stack (the ``repro.kernels`` contract).
    """
    if l.ndim not in (2, 3) or l.shape[-1] != l.shape[-2]:
        raise ValueError(f"trsm expects (n, n) or (B, n, n) factor, got {l.shape}")
    if b.ndim != l.ndim or b.shape[-1] != l.shape[-1] or b.shape[:-2] != l.shape[:-2]:
        raise ValueError(f"bad trsm shapes: {l.shape} x {b.shape}")
    batched = b.ndim == 3
    m, nn = b.shape[-2:]
    bm = min(block_rows, max(8, -(-m // 8) * 8))
    b_pad = _pad_rows(b, bm)
    mp = b_pad.shape[-2]

    lead = (1,) if batched else ()
    batch_dims = b.shape[:-2]
    grid = batch_dims + (mp // bm,)
    _pre = lambda idx: idx[:-1]  # () unbatched, (b,) batched

    out = pl.pallas_call(
        functools.partial(_trsm_kernel, nn=nn, transpose=transpose),
        grid=grid,
        in_specs=[
            pl.BlockSpec(lead + (nn, nn), lambda *idx: _pre(idx) + (0, 0)),
            pl.BlockSpec(lead + (bm, nn), lambda *idx: _pre(idx) + (idx[-1], 0)),
        ],
        out_specs=pl.BlockSpec(
            lead + (bm, nn), lambda *idx: _pre(idx) + (idx[-1], 0)
        ),
        out_shape=out_struct(batch_dims + (mp, nn), out_dtype, l, b_pad),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
        ),
        interpret=interpret,
        name="trsm_t" if transpose else "trsm_n",
    )(l, b_pad)
    return out[..., :m, :]
