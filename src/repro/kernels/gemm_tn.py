"""Pallas TPU kernel for the TN matmul ``C = alpha·AᵀB``.

This is the base-case engine of FastStrassen on TPU. Design points:

* **TN-native**: the kernel contracts dim 0 of both operands with a single
  MXU ``dot_general`` per tile — ``Aᵀ`` is never materialized, addressing the
  paper's observation that ``AᵀA``-style access is cache-hostile (Section 3):
  on TPU the "transpose" happens inside the MXU dataflow.

* **Blocking**: grid ``([B,] n/bn, k/bk, m/bm)`` with the contraction
  dimension minor-most so Mosaic revisits the same output tile across the
  reduction ("arbitrary" semantics); the f32 accumulator lives in a VMEM
  scratch tile and is only written back to HBM once per output tile.

* **Batch**: an optional leading batch grid dimension per the package-wide
  batched-grid contract (see ``repro.kernels`` — leading dim = leaf batch):
  ``(B, m, n) × (B, m, k)`` runs as ONE kernel launch, which is how the
  level-synchronous ``leaf_dispatch='batched'`` recursion lands its whole
  Strassen leaf stack here.

* **VMEM budget**: per grid step the working set is
  ``bm·bn + bm·bk`` input elements + ``bn·bk`` f32 accumulator. The default
  ``(bm, bn, bk) = (512, 256, 256)`` with bf16 inputs is
  512·256·2·2 + 256·256·4 ≈ 0.8 MB — comfortably inside the ~16 MB VMEM and
  every matmul dim a multiple of the 128-lane MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import dot_precision
from repro.kernels.shapes import out_struct

# (bm, bn, bk): contraction block, output-row block, output-col block.
# The constant lives with every other tunable in repro.tune.defaults; the
# autotuner sweeps alternatives per shape (repro.tune.plan → gemm_blocks).
from repro.tune.defaults import GEMM_BLOCKS as DEFAULT_BLOCKS

__all__ = ["gemm_tn_pallas", "gemm_tn_fused_pallas", "DEFAULT_BLOCKS"]


def _gemm_tn_kernel(a_ref, b_ref, c_ref, acc_ref, *, alpha: float, l_axis: int):
    """One ([b,] i, j, l) grid step: acc += A[l,i]ᵀ · B[l,j]."""

    @pl.when(pl.program_id(l_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].reshape(a_ref.shape[-2:])
    b = b_ref[...].reshape(b_ref.shape[-2:])
    acc_ref[...] += jax.lax.dot_general(
        a, b,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=dot_precision(a, b),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(l_axis) == pl.num_programs(l_axis) - 1)
    def _flush():
        c_ref[...] = (alpha * acc_ref[...]).astype(c_ref.dtype).reshape(c_ref.shape)


def _pad_to(x, mult0, mult1):
    m, n = x.shape[-2:]
    pm = (-m) % mult0
    pn = (-n) % mult1
    if pm or pn:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)])
    return x


@functools.partial(
    jax.jit, static_argnames=("alpha", "blocks", "interpret", "out_dtype")
)
def gemm_tn_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    alpha: float = 1.0,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``C = alpha·AᵀB`` with A:(m,n) or (B,m,n), B:(m,k) or (B,m,k).

    Inputs are zero-padded up to block multiples (zero rows of the
    contraction dim contribute nothing; padded output rows/cols are cropped).
    A leading batch dim becomes the leading grid dimension — one launch for
    the whole batch (the ``repro.kernels`` batched-grid contract).
    """
    if a.ndim not in (2, 3) or a.ndim != b.ndim:
        raise ValueError(f"bad TN shapes: {a.shape} x {b.shape}")
    if a.shape[-2] != b.shape[-2] or a.shape[:-2] != b.shape[:-2]:
        raise ValueError(f"bad TN shapes: {a.shape} x {b.shape}")
    batched = a.ndim == 3
    m, n = a.shape[-2:]
    k = b.shape[-1]
    bm, bn, bk = blocks
    # clamp blocks to (padded) problem size to avoid huge pads on small inputs
    bm = min(bm, max(8, -(-m // 8) * 8))
    bn = min(bn, max(128, -(-n // 128) * 128))
    bk = min(bk, max(128, -(-k // 128) * 128))

    a = _pad_to(a, bm, bn)
    b = _pad_to(b, bm, bk)
    mp, np_ = a.shape[-2:]
    kp = b.shape[-1]

    # one spec construction for both layouts: the batched case prepends the
    # batch coordinate to the grid, every block shape, and every index map
    # (same scheme as the syrk kernel).
    lead = (1,) if batched else ()
    batch_dims = a.shape[:-2]
    grid = batch_dims + (np_ // bn, kp // bk, mp // bm)
    l_axis = len(grid) - 1
    _pre = lambda idx: idx[:-3]  # () unbatched, (b,) batched

    out = pl.pallas_call(
        functools.partial(_gemm_tn_kernel, alpha=alpha, l_axis=l_axis),
        grid=grid,
        in_specs=[
            pl.BlockSpec(lead + (bm, bn), lambda *idx: _pre(idx) + (idx[-1], idx[-3])),
            pl.BlockSpec(lead + (bm, bk), lambda *idx: _pre(idx) + (idx[-1], idx[-2])),
        ],
        out_specs=pl.BlockSpec(
            lead + (bn, bk), lambda *idx: _pre(idx) + (idx[-3], idx[-2])
        ),
        out_shape=out_struct(batch_dims + (np_, kp), out_dtype, a, b),
        scratch_shapes=[pltpu.VMEM((bn, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * l_axis + ("arbitrary",),
        ),
        interpret=interpret,
        name="gemm_tn",
    )(a, b)
    return out[..., :n, :k]


# ---------------------------------------------------------------------------
# fused-operand leaf launch (leaf_dispatch='fused')
#
# Per the repro.kernels coefficient-table contract: the operands arrive in
# the block-major leaf-grid layout of `core.strassen._to_blocks` and the
# per-leaf ±1 combinations run in the PROLOGUE of this kernel, against the
# prefetched slot tables — no operand-combination stack is ever written to
# HBM. Each slot is one input ref (the same operand array passed W times
# with a per-slot index map off the prefetched (row, col) tables); the body
# combines them as the same balanced add tree as the trace-time paths
# (sign-0 slots contribute an exact ±0 instead of being dropped — value-
# equal), then runs the identical blocked TN dot as `_gemm_tn_kernel`:
# same (bm, bn)×(bm, bk) chunk shapes, same minor-most contraction order,
# same f32 VMEM accumulation — which is what keeps the fused launch
# bitwise-equal to the unrolled per-leaf kernel calls.
#
# A dead leaf — one whose A slots or whose B slots all carry sign 0 — skips
# its dot and flushes the zeroed accumulator: exact zeros, no MXU work (its
# blocks are still fetched). Strassen's tables never hold one.
# ---------------------------------------------------------------------------


def _gemm_tn_fused_kernel(
    ar, ac, asg, br, bc, bsg, *refs, w: int, alpha: float, t_axis: int, l_axis: int
):
    """One ([g, t, b,] i, j, l) grid step of the fused leaf launch:
    acc += combine(A slots)ᵀ · combine(B slots)."""
    del ar, ac, br, bc  # consumed by the index maps
    a_refs, b_refs = refs[:w], refs[w : 2 * w]
    c_ref, acc_ref = refs[2 * w], refs[2 * w + 1]
    t = pl.program_id(t_axis)

    @pl.when(pl.program_id(l_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def combine(slot_refs, sgn, lo, hi):
        # the balanced slot tree of `core.strassen._combine_slots`, with
        # runtime ±1/0 signs (a sign multiply is exact; adding the ±0 of a
        # dead slot is exact for every non-zero partial sum)
        if hi - lo == 1:
            x = slot_refs[lo][...].reshape(slot_refs[lo].shape[-2:])
            return sgn[t, lo].astype(x.dtype) * x
        mid = (lo + hi) // 2
        return combine(slot_refs, sgn, lo, mid) + combine(slot_refs, sgn, mid, hi)

    def any_slot(sgn):
        live = sgn[t, 0] != 0
        for s in range(1, w):
            live = jnp.logical_or(live, sgn[t, s] != 0)
        return live

    @pl.when(jnp.logical_and(any_slot(asg), any_slot(bsg)))
    def _dot():
        a = combine(a_refs, asg, 0, w)
        b = combine(b_refs, bsg, 0, w)
        acc_ref[...] += jax.lax.dot_general(
            a, b,
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=dot_precision(a, b),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(l_axis) == pl.num_programs(l_axis) - 1)
    def _flush():
        c_ref[...] = (alpha * acc_ref[...]).astype(c_ref.dtype).reshape(c_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("alpha", "blocks", "interpret", "out_dtype")
)
def gemm_tn_fused_pallas(
    a_blocks: jax.Array,
    b_blocks: jax.Array,
    tables,
    *,
    alpha: float = 1.0,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Fused-operand Strassen leaf launch ``P[g·T+t] = alpha·Â(g,t)ᵀB̂(g,t)``.

    ``a_blocks``: ``(G, R, C, [B,] mb, n)`` block-major leaf grids
    (`core.strassen._to_blocks` layout, ``G`` independent groups);
    ``b_blocks`` the same with trailing ``(mb, k)``. ``tables`` =
    ``((a_rows, a_cols, a_sgn), (b_rows, b_cols, b_sgn))``, six ``(T, W)``
    int32 arrays (`core.strassen._slot_tables`): leaf operand ``Â(g, t)``
    is the signed sum of blocks ``a_blocks[g, a_rows[t, w], a_cols[t, w]]``
    over the ``W`` slots. One launch computes all ``G·T`` leaf products —
    the ± combinations run in the kernel prologue, nothing is materialized.
    A leaf whose A signs or whose B signs are all 0 is dead: its product is
    exact zeros and its dot never runs. The tables may be traced, and may
    vary over mesh axes the block grids do not (the result varies over
    both).
    """
    if a_blocks.ndim not in (5, 6) or a_blocks.ndim != b_blocks.ndim:
        raise ValueError(
            f"bad fused block grids: {a_blocks.shape} x {b_blocks.shape}"
        )
    if (
        a_blocks.shape[:3] != b_blocks.shape[:3]
        or a_blocks.shape[:-2] != b_blocks.shape[:-2]
        or a_blocks.shape[-2] != b_blocks.shape[-2]
    ):
        raise ValueError(
            f"bad fused block grids: {a_blocks.shape} x {b_blocks.shape}"
        )
    (a_rows, a_cols, a_sgn), (b_rows, b_cols, b_sgn) = tables
    t_count, w = a_rows.shape
    batched = a_blocks.ndim == 6
    g_count = a_blocks.shape[0]
    m, n = a_blocks.shape[-2:]
    k = b_blocks.shape[-1]
    bm, bn, bk = blocks
    # the same clamp rule as `gemm_tn_pallas` on one leaf's (m, n, k) —
    # identical chunking is what makes fused bitwise-equal to unrolled
    bm = min(bm, max(8, -(-m // 8) * 8))
    bn = min(bn, max(128, -(-n // 128) * 128))
    bk = min(bk, max(128, -(-k // 128) * 128))

    a_blocks = _pad_to(a_blocks, bm, bn)
    b_blocks = _pad_to(b_blocks, bm, bk)
    mp, np_ = a_blocks.shape[-2:]
    kp = b_blocks.shape[-1]

    lead = (1,) if batched else ()
    batch_dims = a_blocks.shape[3:-2]
    grid = (g_count, t_count) + batch_dims + (np_ // bn, kp // bk, mp // bm)
    t_axis, l_axis = 1, len(grid) - 1
    _pre = lambda idx: idx[2:-3]  # () unbatched, (b,) batched

    def _a_index(slot):
        def index(*args):
            idx, (rows, cols) = args[: len(grid)], args[len(grid) : len(grid) + 2]
            return (idx[0], rows[idx[1], slot], cols[idx[1], slot]) + _pre(
                idx
            ) + (idx[-1], idx[-3])

        return index

    def _b_index(slot):
        def index(*args):
            idx, rows, cols = args[: len(grid)], args[len(grid) + 3], args[len(grid) + 4]
            return (idx[0], rows[idx[1], slot], cols[idx[1], slot]) + _pre(
                idx
            ) + (idx[-1], idx[-2])

        return index

    def _c_index(*args):
        idx = args[: len(grid)]
        return (idx[0] * t_count + idx[1],) + _pre(idx) + (idx[-3], idx[-2])

    prefetch = tuple(jnp.asarray(x) for x in (a_rows, a_cols, a_sgn,
                                              b_rows, b_cols, b_sgn))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1) + lead + (bm, bn), _a_index(s)) for s in range(w)
        ]
        + [
            pl.BlockSpec((1, 1, 1) + lead + (bm, bk), _b_index(s)) for s in range(w)
        ],
        out_specs=pl.BlockSpec((1,) + lead + (bn, bk), _c_index),
        scratch_shapes=[pltpu.VMEM((bn, bk), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _gemm_tn_fused_kernel, w=w, alpha=alpha, t_axis=t_axis, l_axis=l_axis
        ),
        grid_spec=grid_spec,
        out_shape=out_struct(
            (g_count * t_count,) + batch_dims + (np_, kp), out_dtype,
            a_blocks, b_blocks, *prefetch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * l_axis + ("arbitrary",),
        ),
        interpret=interpret,
        name="gemm_tn_fused",
    )(*prefetch, *([a_blocks] * w), *([b_blocks] * w))
    return out[..., :n, :k]
