"""Pallas TPU kernel for the symmetric product ``C = alpha·AᵀA`` (syrk).

This is the base-case engine of ATA on TPU and carries the paper's key
block-level saving: **only lower-triangular output blocks are computed**
(the strictly-upper blocks are never visited by the grid), halving both MXU
work and HBM write traffic versus a general TN matmul — the TPU analogue of
the paper computing only ``low(C)`` at every level.

Grid design: a **packed triangular grid** ``([B,] T, m/bm)`` where
``T = nb·(nb+1)/2`` enumerates the lower-triangular block pairs. The
optional leading batch dimension follows the package-wide batched-grid
contract (see the ``repro.kernels`` docstring: leading dim = leaf batch,
one launch per stack, never vmap-of-pallas — the batched-leaf recursion
lands all its diagonal leaves here in one call). Pallas TPU grids are
rectangular, so the block coordinates are recovered inside the index maps
from the triangular index ``t``:

    i = ⌊(√(8t+1) − 1)/2⌋,   j = t − i(i+1)/2      (j ≤ i)

(computed in f32 — exact for every t < 2²³, far beyond any realistic block
count — with an integer correction step to be safe at the boundaries).
The contraction over ``m`` runs in the minor-most grid dimension with an f32
VMEM scratch accumulator, exactly like ``gemm_tn``.

Output modes — both mirror-free (the seed's ``tril + mirror`` post-pass over
n² elements is gone):

* ``out='packed'``: the kernel writes the ``T`` lower-triangular blocks
  straight into packed ``(T, bn, bn)`` storage — ``nb(nb+1)/2`` output
  blocks allocated instead of ``nb²`` — returned as a
  :class:`repro.core.symmetric.SymmetricMatrix`. Diagonal tiles are
  symmetrized *in-kernel* at tile granularity (an O(n·bn) cost).

* ``out='dense'``: in-kernel **dual-write**. The contraction grid dimension
  carries one extra trailing step per block pair: after the lower block
  ``C[i,j]`` is flushed, the extra step retargets the output index map at
  ``C[j,i]`` and stores the transposed tile from the still-resident VMEM
  accumulator (diagonal pairs re-store the symmetrized tile instead). Every
  one of the nb² blocks is written exactly once; the public output is
  bitwise symmetric with no elementwise post-pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import dot_precision
from repro.core.symmetric import SymmetricMatrix, default_block_size, sym_tile
from repro.kernels.shapes import out_struct

# (bm, bn): contraction block, output block (output tiles are bn × bn).
# The constant lives with every other tunable in repro.tune.defaults; the
# autotuner sweeps alternatives per shape (repro.tune.plan → syrk_blocks).
from repro.tune.defaults import SYRK_BLOCKS as DEFAULT_BLOCKS

__all__ = ["syrk_pallas", "syrk_gather_pallas", "DEFAULT_BLOCKS"]


def _tri_coords(t):
    """Map packed triangular index t -> (i, j) with j <= i, traceably."""
    tf = t.astype(jnp.float32)
    i = jnp.floor((jnp.sqrt(8.0 * tf + 1.0) - 1.0) / 2.0).astype(jnp.int32)
    # integer boundary corrections (defensive against fp rounding)
    i = jnp.where((i + 1) * (i + 2) // 2 <= t, i + 1, i)
    i = jnp.where(i * (i + 1) // 2 > t, i - 1, i)
    j = t - i * (i + 1) // 2
    return i, j


def _syrk_kernel(
    ai_ref, aj_ref, c_ref, acc_ref, *, alpha: float, t_axis: int, n_l: int, packed: bool
):
    """One grid step: acc += A[l, i(t)]ᵀ · A[l, j(t)], plus the mode's writes.

    In dense (dual-write) mode the contraction axis has ``n_l + 1`` steps;
    the trailing step stores the mirrored tile while the accumulator is still
    resident in VMEM.
    """
    l_axis = t_axis + 1
    l = pl.program_id(l_axis)
    t = pl.program_id(t_axis)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(l < n_l)
    def _accum():
        ai = ai_ref[...].reshape(ai_ref.shape[-2:])
        aj = aj_ref[...].reshape(aj_ref.shape[-2:])
        acc_ref[...] += jax.lax.dot_general(
            ai, aj,
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=dot_precision(ai, aj),
            preferred_element_type=jnp.float32,
        )

    if packed:

        @pl.when(l == n_l - 1)
        def _flush_packed():
            out = (alpha * acc_ref[...]).astype(c_ref.dtype)
            i, j = _tri_coords(t)
            c_ref[...] = jnp.where(i == j, sym_tile(out), out).reshape(c_ref.shape)

    else:

        @pl.when(l == n_l - 1)
        def _flush_lower():
            out = (alpha * acc_ref[...]).astype(c_ref.dtype)
            c_ref[...] = out.reshape(c_ref.shape)

        @pl.when(l == n_l)
        def _flush_mirror():
            out = (alpha * acc_ref[...]).astype(c_ref.dtype)
            i, j = _tri_coords(t)
            # off-diagonal: the (j, i) block is the transposed tile; diagonal
            # pairs re-store the symmetrized tile into the same (i, i) slot.
            c_ref[...] = jnp.where(i == j, sym_tile(out), out.T).reshape(c_ref.shape)


def _pad_to(x, mult0, mult1):
    m, n = x.shape[-2:]
    pm = (-m) % mult0
    pn = (-n) % mult1
    if pm or pn:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)])
    return x


@functools.partial(
    jax.jit, static_argnames=("alpha", "blocks", "interpret", "out_dtype", "out")
)
def syrk_pallas(
    a: jax.Array,
    *,
    alpha: float = 1.0,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
    out_dtype=jnp.float32,
    out: str = "dense",
):
    """``C = alpha·AᵀA`` with A:(m,n) or (B,m,n).

    ``out='dense'`` → ``(..., n, n)``, bitwise symmetric, written once per
    block by the in-kernel dual-write (no mirror post-pass).
    ``out='packed'`` → :class:`SymmetricMatrix` holding the ``nb(nb+1)/2``
    lower-triangular blocks the grid computes — nothing else is allocated.
    """
    if a.ndim not in (2, 3):
        raise ValueError(f"syrk expects (m, n) or (B, m, n) input, got {a.shape}")
    if out not in ("dense", "packed"):
        raise ValueError(f"unknown output mode {out!r}; use 'dense' or 'packed'")
    batched = a.ndim == 3
    m, n = a.shape[-2:]
    bm, bn = blocks
    bm = min(bm, max(8, -(-m // 8) * 8))
    if out == "packed":
        # packed storage shares one block-size clamp across ALL producers
        # (symmetric.default_block_size) regardless of backend, so layouts
        # are always add-compatible and a small matrix is never padded up to
        # a huge single block. The clamp yields lane-unaligned blocks for
        # ragged n (e.g. 104 for n=200); Mosaic surfaces its own error for
        # sizes it cannot tile — on TPU, keep n and the requested block at
        # multiples of 128 (production gram shapes already are).
        bn = default_block_size(n, bn)
    else:
        bn = min(bn, max(128, -(-n // 128) * 128))

    a = _pad_to(a, bm, bn)
    mp, np_ = a.shape[-2:]
    nb = np_ // bn
    t_total = nb * (nb + 1) // 2
    n_l = mp // bm
    t_axis = 1 if batched else 0

    kernel = functools.partial(
        _syrk_kernel,
        alpha=alpha,
        t_axis=t_axis,
        n_l=n_l,
        packed=(out == "packed"),
    )
    # dense mode appends the dual-write step to the contraction axis.
    l_steps = n_l if out == "packed" else n_l + 1
    l_clamp = lambda l: jnp.minimum(l, n_l - 1)

    # one spec construction for both layouts: the batched case prepends the
    # batch coordinate to the grid, every block shape, and every index map.
    lead = (1,) if batched else ()
    batch_dims = a.shape[:-2]
    grid = batch_dims + (t_total, l_steps)
    _pre = lambda idx: idx[:-2]  # () unbatched, (b,) batched

    def _a_index(which):
        return lambda *idx: _pre(idx) + (
            l_clamp(idx[-1]), _tri_coords(idx[-2])[which]
        )

    in_specs = [
        pl.BlockSpec(lead + (bm, bn), _a_index(0)),
        pl.BlockSpec(lead + (bm, bn), _a_index(1)),
    ]
    if out == "packed":
        out_specs = pl.BlockSpec(
            lead + (1, bn, bn), lambda *idx: _pre(idx) + (idx[-2], 0, 0)
        )
        out_shape = out_struct(batch_dims + (t_total, bn, bn), out_dtype, a)
    else:

        def _c_index(*idx):
            i, j = _tri_coords(idx[-2])
            lower = idx[-1] < n_l
            return _pre(idx) + (jnp.where(lower, i, j), jnp.where(lower, j, i))

        out_specs = pl.BlockSpec(lead + (bn, bn), _c_index)
        out_shape = out_struct(batch_dims + (np_, np_), out_dtype, a)
    dim_sem = ("parallel",) * (len(grid) - 1) + ("arbitrary",)

    raw = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=dim_sem),
        interpret=interpret,
        name="syrk_packed" if out == "packed" else "syrk_dual",
    )(a, a)

    if out == "packed":
        return SymmetricMatrix(raw, n=n, bn=bn)
    return raw[..., :n, :n]


# ---------------------------------------------------------------------------
# gathered diagonal-leaf launch (leaf_dispatch='fused')
#
# Per the repro.kernels coefficient-table contract: the ATA recursion's
# fused dispatch hands this kernel the block-major leaf grid of
# `core.strassen._to_blocks` plus prefetched (row, col) index tables, and
# the PROLOGUE's index maps pull each diagonal slab straight out of the
# grid — the `(4^L, …)` gathered stack of the batched dispatch is never
# materialized. The grid, kernel body (`_syrk_kernel`, dense dual-write)
# and block clamps are identical to `syrk_pallas` on the equivalent
# stacked input, which keeps the fused diagonal bitwise-equal to the
# batched one. Diagonal coefficients are trivially +1, so the tables here
# are pure gather indices — the ± structure lives in the gemm twin
# (`gemm_tn_fused_pallas`).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("alpha", "blocks", "interpret", "out_dtype")
)
def syrk_gather_pallas(
    a_blocks: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    *,
    alpha: float = 1.0,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """``C[s] = alpha·ÂᵀÂ`` with ``Â = a_blocks[rows[s], cols[s]]``.

    ``a_blocks``: ``(R, C, [B,] mL, nL)`` block-major leaf grid;
    ``rows``/``cols``: ``(S,)`` int32 gather tables. Returns the dense
    ``(S, [B,] nL, nL)`` stack — one launch for every diagonal leaf, the
    gather running in the kernel's index maps.
    """
    if a_blocks.ndim not in (4, 5):
        raise ValueError(f"bad gathered block grid: {a_blocks.shape}")
    batched = a_blocks.ndim == 5
    s_count = rows.shape[0]
    m, n = a_blocks.shape[-2:]
    bm, bn = blocks
    # the same clamp rule as `syrk_pallas` dense mode on one (mL, nL) leaf
    bm = min(bm, max(8, -(-m // 8) * 8))
    bn = min(bn, max(128, -(-n // 128) * 128))

    a_blocks = _pad_to(a_blocks, bm, bn)
    mp, np_ = a_blocks.shape[-2:]
    nb = np_ // bn
    t_total = nb * (nb + 1) // 2
    n_l = mp // bm
    t_axis = 2 if batched else 1

    def kernel(rows_ref, cols_ref, *refs):
        del rows_ref, cols_ref  # consumed by the index maps
        _syrk_kernel(*refs, alpha=alpha, t_axis=t_axis, n_l=n_l, packed=False)

    l_clamp = lambda l: jnp.minimum(l, n_l - 1)

    lead = (1,) if batched else ()
    batch_dims = a_blocks.shape[2:-2]
    grid = (s_count,) + batch_dims + (t_total, n_l + 1)
    _pre = lambda idx: idx[1:-2]  # () unbatched, (b,) batched

    def _a_index(which):
        def index(*args):
            idx, rows_ref, cols_ref = args[:-2], args[-2], args[-1]
            return (rows_ref[idx[0]], cols_ref[idx[0]]) + _pre(idx) + (
                l_clamp(idx[-1]), _tri_coords(idx[-2])[which]
            )

        return index

    def _c_index(*args):
        idx = args[:-2]
        i, j = _tri_coords(idx[-2])
        lower = idx[-1] < n_l
        return (idx[0],) + _pre(idx) + (
            jnp.where(lower, i, j), jnp.where(lower, j, i)
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1) + lead + (bm, bn), _a_index(0)),
            pl.BlockSpec((1, 1) + lead + (bm, bn), _a_index(1)),
        ],
        out_specs=pl.BlockSpec((1,) + lead + (bn, bn), _c_index),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
    )
    raw = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_struct(
            (s_count,) + batch_dims + (np_, np_), out_dtype, a_blocks
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",),
        ),
        interpret=interpret,
        name="syrk_gather",
    )(jnp.asarray(rows), jnp.asarray(cols), a_blocks, a_blocks)
    return raw[..., :n, :n]
