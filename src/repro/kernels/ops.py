"""Public jit'd wrappers for the Pallas kernels.

Both package-wide contracts — interpret-mode resolution and the batched
grid (leading dim = leaf batch, one launch per stack) — are stated once in
the ``repro.kernels`` package docstring; the wrappers here implement them.
``repro.core.ata``/``strassen_tn`` accept these as ``base_syrk``/``base_dot``
so the whole recursion bottoms out in the kernels — including the
level-synchronous ``leaf_dispatch='batched'`` recursion, which hands each
wrapper its entire leaf stack as the one leading batch dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.gemm_tn import DEFAULT_BLOCKS as GEMM_BLOCKS
from repro.kernels.gemm_tn import gemm_tn_fused_pallas, gemm_tn_pallas
from repro.kernels.potrf import potrf_pallas
from repro.kernels.syrk import DEFAULT_BLOCKS as SYRK_BLOCKS
from repro.kernels.syrk import syrk_pallas
from repro.kernels.trsm import trsm_pallas

__all__ = [
    "syrk", "gemm_tn", "gemm_tn_fused", "potrf", "trsm",
    "interpret_default",
]


def interpret_default() -> bool:
    """Pallas interpret mode unless running on a real TPU.

    The canonical resolution of ``interpret=None`` for every wrapper in
    this module (see the ``repro.kernels`` package docstring): compiled
    Mosaic on TPU, interpret mode on any other backend.
    """
    return jax.default_backend() != "tpu"


def syrk(
    a,
    *,
    alpha: float = 1.0,
    blocks=None,
    plan=None,
    interpret=None,
    out_dtype=jnp.float32,
    out: str = "dense",
):
    """``alpha·AᵀA`` via the Pallas lower-triangular syrk kernel.

    Accepts ``(m, n)`` or batched ``(B, m, n)`` input — the batch runs as
    the leading grid dimension, one launch for the whole stack (the
    ``repro.kernels`` batched-grid contract). ``out='packed'`` returns the
    mirror-free :class:`repro.core.symmetric.SymmetricMatrix` form;
    ``out='dense'`` uses the in-kernel dual-write (no mirror post-pass).
    Block shapes come from ``blocks``, else the ``plan`` (a
    :class:`repro.tune.Plan`), else the tuned defaults. ``interpret=None``
    resolves via :func:`interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    if blocks is None and plan is not None:
        blocks = plan.syrk_blocks
    obs.metrics.inc("kernels.launch.syrk")
    with obs.span("kernels.syrk", interpret=interpret):
        return syrk_pallas(
            a,
            alpha=alpha,
            blocks=tuple(blocks or SYRK_BLOCKS),
            interpret=interpret,
            out_dtype=out_dtype,
            out=out,
        )


def gemm_tn(
    a,
    b,
    *,
    alpha: float = 1.0,
    blocks=None,
    plan=None,
    interpret=None,
    out_dtype=jnp.float32,
):
    """``alpha·AᵀB`` via the Pallas TN matmul kernel.

    Accepts ``(m, n) × (m, k)`` or batched ``(B, m, n) × (B, m, k)`` — the
    batch is the leading grid dimension, one launch for the whole stack
    (the ``repro.kernels`` batched-grid contract; this is where the
    batched-leaf recursion lands its ``7^L`` Strassen leaves). Blocks from
    the argument, else the ``plan``, else the tuned defaults;
    ``interpret=None`` resolves via :func:`interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    if blocks is None and plan is not None:
        blocks = plan.gemm_blocks
    obs.metrics.inc("kernels.launch.gemm_tn")
    with obs.span("kernels.gemm_tn", interpret=interpret):
        return gemm_tn_pallas(
            a,
            b,
            alpha=alpha,
            blocks=tuple(blocks or GEMM_BLOCKS),
            interpret=interpret,
            out_dtype=out_dtype,
        )


def gemm_tn_fused(
    a_blocks,
    b_blocks,
    tables,
    *,
    alpha: float = 1.0,
    blocks=None,
    plan=None,
    interpret=None,
    out_dtype=jnp.float32,
):
    """All ``G·T`` table-addressed leaf products in ONE launch.

    The ``repro.kernels`` coefficient-table contract: ``a_blocks``/
    ``b_blocks`` are block-major leaf grids (`core.strassen._to_blocks`'s
    layout), ``tables`` the per-leaf ``(rows, cols, sign)`` slot tables;
    each leaf's operands are gathered and combined in the kernel prologue
    against the prefetched tables. Its one caller is the distributed tile
    stack (`core.distributed._direct_stack`), whose one-slot leaves read
    stripe ``i`` as A and stripe ``j`` as B. Blocks from the argument,
    else the ``plan``, else the tuned defaults; ``interpret=None``
    resolves via :func:`interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    if blocks is None and plan is not None:
        blocks = plan.gemm_blocks
    obs.metrics.inc("kernels.launch.gemm_tn_fused")
    with obs.span("kernels.gemm_tn_fused", interpret=interpret):
        return gemm_tn_fused_pallas(
            a_blocks,
            b_blocks,
            tables,
            alpha=alpha,
            blocks=tuple(blocks or GEMM_BLOCKS),
            interpret=interpret,
            out_dtype=out_dtype,
        )


def potrf(a, *, interpret=None, out_dtype=jnp.float32):
    """Lower Cholesky factor of SPD tile(s) via the Pallas potrf kernel.

    Accepts ``(n, n)`` or a stacked ``(B, n, n)`` — the stack runs as the
    leading grid dimension, one launch for the whole batch (the
    ``repro.kernels`` batched-grid contract: a batched Shampoo stat stack
    factors its diagonal blocks in ONE launch per block column).
    ``interpret=None`` resolves via :func:`interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    obs.metrics.inc("kernels.launch.potrf")
    with obs.span("kernels.potrf", interpret=interpret):
        return potrf_pallas(a, interpret=interpret, out_dtype=out_dtype)


def trsm(l, b, *, transpose=True, interpret=None, out_dtype=jnp.float32):
    """Triangular panel solve ``X·Lᵀ = B`` (or ``X·L = B``) via the Pallas
    trsm kernel — the blocked-Cholesky panel op and the building block of
    the packed forward/backward substitution (``repro.solve.triangular``).

    Accepts ``(n, n) × (m, n)`` or stacked ``(B, n, n) × (B, m, n)`` — the
    stack is the leading grid dimension, one launch per panel stack.
    ``interpret=None`` resolves via :func:`interpret_default`.
    """
    if interpret is None:
        interpret = interpret_default()
    obs.metrics.inc("kernels.launch.trsm")
    with obs.span("kernels.trsm", interpret=interpret):
        return trsm_pallas(l, b, transpose=transpose, interpret=interpret,
                           out_dtype=out_dtype)
