"""Generalized rectangular Strassen for the TN product ``C = alpha·AᵀB``.

This is the paper's FastStrassen (Algorithm 1, lines 14-18) adapted to JAX/TPU:

* **Trace-time recursion** — the recursion runs in Python over static shapes
  during ``jax.jit`` tracing and unrolls into an XLA graph. XLA's buffer
  assignment plays the role of the paper's pre-allocated ``M, P, Q`` scratch
  (Section 3.3): no per-level allocation happens at run time.

* **TN form is preserved all the way down.** The paper notes that row-major
  ``AᵀA`` is cache-hostile because access is column-wise; on TPU the fix is to
  never materialize ``Aᵀ``. With ``X = Aᵀ`` split into quadrants,
  ``X11 = A11ᵀ, X12 = A21ᵀ, X21 = A12ᵀ, X22 = A22ᵀ``, every one of Strassen's
  seven products is again a TN product of *combinations of A blocks in their
  original orientation* against combinations of B blocks. The base case hands
  a TN ``dot_general`` (contracting dims ``((0,),(0,))``) to the MXU, which
  consumes the transpose inside its dataflow for free.

* **Odd sizes** — handled by **one root pad**: the dispatch computes the
  recursion depth ``L`` up front, zero-pads each dim once to a multiple of
  ``2^L`` (the paper's "virtual padding" hoisted out of the levels — a single
  ``lax.pad`` instead of one per level), and crops once at the root. Interior
  levels then always split exactly in half.

* **Variants** — ``'strassen'`` (paper-faithful: 7 mults, 18 adds) and
  ``'winograd'`` (beyond-paper: 7 mults, 15 adds; lowers the memory roofline
  term).

* **Leaf dispatch** — three formulations of the same arithmetic
  (``leaf_dispatch`` on the plan, DESIGN.md §2):

  - ``'unrolled'`` (legacy): the recursion emits one ``base_dot`` per leaf —
    ``7^L`` separate dots in the jaxpr.
  - ``'batched'``: an iterative, level-synchronous schedule. Each level
    *encodes* Strassen's ±1 operand combinations into a stacked tensor with a
    leading leaf-batch axis (pure adds/subs on ``(7^ℓ, m/2^ℓ, n/2^ℓ)``
    stacks), **all** ``7^L`` leaf products run as *one* batched TN dot, and
    the result is *decoded* level-by-level (the c11..c22 recombinations on
    stacks, quadrant concatenation). O(L) ops in the jaxpr instead of
    O(7^L); bitwise-equal to the unrolled form (tested).
  - ``'fused'``: no materialized operand combinations at all. Each leaf
    operand is described by a per-leaf ±1 *slot table* over the root
    leaf-block grid (built at trace time); the combinations are either
    folded into the Pallas leaf kernel's prologue (coefficient tables ride
    in as scalar-prefetch operands) or built as trace-time slice gathers on
    the XLA path. One leaf launch, shared decode, bitwise-equal to the
    other two (tested); classical variant only.

* **Base case** — recursion cuts off when any dimension ≤ ``n_base`` and hands
  the tile to ``base_dot`` (default: MXU-dense ``dot_general``; the Pallas
  ``gemm_tn`` kernel via ``repro.kernels.ops`` on TPU). On TPU the cutoff is
  the analogue of the paper's "fits in cache": below it, Strassen's extra VPU
  additions cost more than the MXU saves.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.precision import dot_precision
from repro.tune.defaults import DEFAULT_N_BASE  # re-export (tunables live there)

__all__ = ["strassen_tn", "DEFAULT_N_BASE", "resolve_tunables"]


def resolve_tunables(
    plan,
    n_base,
    variant,
    packed_block,
    *,
    op: str,
    m: int,
    n: int,
    k: Optional[int] = None,
    batch: int = 0,
    dtype: str = "float32",
    out: str = "dense",
    leaf_dispatch: Optional[str] = None,
):
    """Fill unset tunables (shared by `strassen_tn`, `ata`, `distributed`).

    Three regimes, in order:

    * a ``plan`` was handed in → unset args come from it;
    * no algorithm tunable (``n_base``/``variant``) was pinned → consult the
      ``repro.tune.plan`` front door (analytic model / plan cache) — every
      default dispatch is planned (``packed_block`` and ``leaf_dispatch``
      are layout/scheduling parameters, not algorithm choices: pinning one
      of them alone does not bypass the planner — ``leaf_dispatch`` never
      changes *values*, only how the leaves reach the hardware);
    * the caller pinned an algorithm tunable manually → fill the rest with
      the static paper-faithful defaults (``repro.tune.defaults``),
      **without** consulting the planner, so explicit calls stay bitwise
      reproducible regardless of cache state.

    Returns ``(plan_or_None, n_base, variant, packed_block, leaf_dispatch)``;
    a plan with ``algorithm='dense'`` comes back with ``n_base`` covering the
    whole operand, which is how "classical one-dot dispatch" is expressed to
    the recursion.
    """
    from repro.tune import defaults as _defaults

    if plan is None and n_base is None and variant is None:
        from repro.tune import plan as _plan_fn

        plan = _plan_fn(op=op, m=m, n=n, k=k, batch=batch, dtype=dtype, out=out)
    if plan is not None:
        n_base = plan.n_base if n_base is None else n_base
        variant = plan.variant if variant is None else variant
        packed_block = plan.packed_block if packed_block is None else packed_block
        if leaf_dispatch is None:
            # getattr: plans deserialized from pre-leaf_dispatch caches
            leaf_dispatch = getattr(plan, "leaf_dispatch", None)
        if plan.algorithm == "dense":
            n_base = max(n_base, m, n, k or n)
    else:
        n_base = _defaults.DEFAULT_N_BASE if n_base is None else n_base
        variant = _defaults.DEFAULT_VARIANT if variant is None else variant
        packed_block = (
            _defaults.DEFAULT_PACKED_BLOCK if packed_block is None else packed_block
        )
    if leaf_dispatch is None:
        leaf_dispatch = _defaults.DEFAULT_LEAF_DISPATCH
    if leaf_dispatch not in ("unrolled", "batched", "fused"):
        raise ValueError(
            f"unknown leaf_dispatch {leaf_dispatch!r}; "
            "use 'unrolled', 'batched' or 'fused'"
        )
    return plan, n_base, variant, packed_block, leaf_dispatch


def _plan_base_fns(plan, base_syrk, base_dot):
    """Pallas base kernels per the plan (when the caller supplied none)."""
    if plan is not None and plan.use_kernels and base_syrk is None and base_dot is None:
        from repro.tune.apply import base_fns

        return base_fns(plan)
    return base_syrk, base_dot


def _plan_fused_fns(plan):
    """(fused_syrk, fused_dot) Pallas fused leaf launches per the plan —
    ``(None, None)`` keeps the XLA trace-time gather path."""
    if plan is not None and plan.use_kernels:
        from repro.tune.apply import fused_fns

        return fused_fns(plan)
    return None, None


def _dot_tn(a, b, acc_dtype):
    """Base-case ``AᵀB`` without materializing ``Aᵀ`` (TN dot_general).

    Operates on the last two dims; any leading dims are batch dims (used by
    the batched gram path in ``repro.core.ata.ata_batched`` and by the
    batched leaf dispatch, whose leading dim is the leaf stack).
    """
    nb = a.ndim - 2
    batch = tuple(range(nb))
    return jax.lax.dot_general(
        a,
        b,
        dimension_numbers=(((nb,), (nb,)), (batch, batch)),
        precision=dot_precision(a, b),
        preferred_element_type=acc_dtype,
    )


# ---------------------------------------------------------------------------
# root padding (the per-level _pad_even of the seed, hoisted to dispatch)
# ---------------------------------------------------------------------------


def tree_depth(dims, n_base: int) -> int:
    """Levels the recursion performs: smallest ``L`` with
    ``min(⌈d/2^L⌉) ≤ n_base`` — identical to the legacy per-level
    pad-to-even recursion depth (⌈⌈d/2⌉/2⌉ = ⌈d/4⌉)."""
    L = 0
    while min(-(-d // (1 << L)) for d in dims) > n_base:
        L += 1
    return L


def _pad_root(x, L: int):
    """Zero-pad the last two dims of ``x`` up to multiples of ``2^L`` —
    the one root pad; every interior level then splits exactly in half."""
    step = 1 << L
    m, n = x.shape[-2:]
    pm = (-m) % step
    pn = (-n) % step
    if pm or pn:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)])
    return x


def _quadrants(x):
    m, n = x.shape[-2:]
    m2, n2 = m // 2, n // 2
    return (
        x[..., :m2, :n2],
        x[..., :m2, n2:],
        x[..., m2:, :n2],
        x[..., m2:, n2:],
    )


# ---------------------------------------------------------------------------
# unrolled leaf dispatch (legacy): one base_dot per leaf
# ---------------------------------------------------------------------------


def _rec_strassen(a, b, n_base, base_dot, acc_dtype):
    """Classical Strassen recursion on the TN product (7 mults, 18 adds).

    Operands arrive root-padded (dims divisible by 2 at every level above
    the cutoff), so no per-level padding or cropping happens here.
    """
    m, n = a.shape[-2:]
    k = b.shape[-1]
    if min(m, n, k) <= n_base:
        return base_dot(a, b)

    rec = functools.partial(
        _rec_strassen, n_base=n_base, base_dot=base_dot, acc_dtype=acc_dtype
    )
    # With X = Aᵀ: X11=A11ᵀ X12=A21ᵀ X21=A12ᵀ X22=A22ᵀ. Classical formulas:
    with obs.span("strassen.encode", n=n):
        a11, a12, a21, a22 = _quadrants(a)
        b11, b12, b21, b22 = _quadrants(b)
        operands = [
            (a11 + a22, b11 + b22),  # (X11+X22)(Y11+Y22)
            (a12 + a22, b11),        # (X21+X22)Y11
            (a11, b12 - b22),        # X11(Y12-Y22)
            (a22, b21 - b11),        # X22(Y21-Y11)
            (a11 + a21, b22),        # (X11+X12)Y22
            (a12 - a11, b11 + b12),  # (X21-X11)(Y11+Y12)
            (a21 - a22, b21 + b22),  # (X12-X22)(Y21+Y22)
        ]
    m1, m2, m3, m4, m5, m6, m7 = (rec(x, y) for x, y in operands)

    # Balanced association (not the textbook left-to-right chain): the fused
    # leaf dispatch evaluates its per-leaf slot tables as perfect binary add
    # trees, and keeping every dispatch on the same association keeps the
    # three of them bitwise-equal.
    with obs.span("strassen.decode", n=n):
        c11 = (m1 + m4) + (m7 - m5)
        c12 = m3 + m5
        c21 = m2 + m4
        c22 = (m1 - m2) + (m3 + m6)
        return jnp.block([[c11, c12], [c21, c22]])


def _rec_winograd(a, b, n_base, base_dot, acc_dtype):
    """Strassen-Winograd recursion (7 mults, 15 adds) — beyond-paper variant."""
    m, n = a.shape[-2:]
    k = b.shape[-1]
    if min(m, n, k) <= n_base:
        return base_dot(a, b)

    rec = functools.partial(
        _rec_winograd, n_base=n_base, base_dot=base_dot, acc_dtype=acc_dtype
    )
    # X blocks in A-space: X11=A11 X12=A21 X21=A12 X22=A22 (all transposed
    # implicitly by the TN product). Winograd schedule:
    with obs.span("strassen.encode", n=n):
        a11, a12, a21, a22 = _quadrants(a)
        b11, b12, b21, b22 = _quadrants(b)
        s1 = a12 + a22          # X21 + X22
        s2 = s1 - a11           # S1 - X11
        s3 = a11 - a12          # X11 - X21
        s4 = a21 - s2           # X12 - S2
        t1 = b12 - b11          # Y12 - Y11
        t2 = b22 - t1           # Y22 - T1
        t3 = b22 - b12          # Y22 - Y12
        t4 = t2 - b21           # T2 - Y21

    p1 = rec(a11, b11)      # X11 Y11
    p2 = rec(a21, b21)      # X12 Y21
    p3 = rec(s4, b22)       # S4 Y22
    p4 = rec(a22, t4)       # X22 T4
    p5 = rec(s1, t1)        # S1 T1
    p6 = rec(s2, t2)        # S2 T2
    p7 = rec(s3, t3)        # S3 T3

    with obs.span("strassen.decode", n=n):
        u2 = p1 + p6
        u3 = u2 + p7
        u4 = u2 + p5

        c11 = p1 + p2
        c12 = u4 + p3
        c21 = u3 - p4
        c22 = u3 + p5

        return jnp.block([[c11, c12], [c21, c22]])


# ---------------------------------------------------------------------------
# batched leaf dispatch: level-synchronous encode → one dot → decode
#
# Stack layout (block-major): (S, R, C, *batch, mb, nb) — the leaf-batch
# axis is ALWAYS axis 0, followed by the entry's leaf-block grid (R row
# blocks × C column blocks of leaf-sized (mb, nb) tiles), then any operand
# batch dims. The operands are transposed into this layout ONCE at the root
# (`_to_blocks`), so every level's quadrant split is a *leading-axis* slice
# of whole leaf blocks — large contiguous chunks, not the row-fragment
# strides that a (..., m, n) quadrant slice produces — and the final leaf
# stack is the base dot's batch layout with no further copy. One encode
# level multiplies S by 7 (child s·7+t is product t of parent s) and halves
# R, C; one decode level does the reverse; `_unblock` undoes the root
# blocking after the last decode.
#
# The same elementwise adds/subs as the unrolled recursion run on the
# stacks, in the same order, on the same values — layout is the only thing
# that differs — so the two dispatches are bitwise-equal (tested).
# ---------------------------------------------------------------------------


def _to_blocks(x, L):
    """(*batch, M, N) → block-major (2^L, 2^L, *batch, M/2^L, N/2^L)."""
    R = 1 << L
    *batch, M, N = x.shape
    nbd = len(batch)
    x = x.reshape(*batch, R, M // R, R, N // R)
    x = jnp.moveaxis(x, nbd, 0)       # row-block axis first
    x = jnp.moveaxis(x, nbd + 2, 1)   # column-block axis second
    return x


def _unblock(x):
    """(S, R, C, *batch, h, w) → (S, *batch, R·h, C·w) — the inverse root
    transpose, applied once after the last decode level."""
    S, R, C = x.shape[:3]
    batch = x.shape[3:-2]
    h, w = x.shape[-2:]
    nbd = len(batch)
    perm = (0,) + tuple(range(3, 3 + nbd)) + (1, 3 + nbd, 2, 4 + nbd)
    return x.transpose(perm).reshape(S, *batch, R * h, C * w)


def _quadrants_b(x):
    """Quadrants of a block-major stack — slices of the block-grid axes."""
    m2, n2 = x.shape[1] // 2, x.shape[2] // 2
    return (
        x[:, :m2, :n2],
        x[:, :m2, n2:],
        x[:, m2:, :n2],
        x[:, m2:, n2:],
    )


def _stack7(parts):
    """Stack 7 per-parent combinations into the leaf-batch axis: (S, ...)
    → (7S, ...) with child index ``s·7 + t``."""
    e = jnp.stack(parts, axis=1)
    return e.reshape(e.shape[0] * 7, *e.shape[2:])


def _encode_strassen(A, B):
    """One encode level: 7 operand combinations per parent, halved grids."""
    a11, a12, a21, a22 = _quadrants_b(A)
    b11, b12, b21, b22 = _quadrants_b(B)
    ea = _stack7([a11 + a22, a12 + a22, a11, a22, a11 + a21, a12 - a11, a21 - a22])
    eb = _stack7([b11 + b22, b11, b12 - b22, b21 - b11, b22, b11 + b12, b21 + b22])
    return ea, eb


def _encode_winograd(A, B):
    a11, a12, a21, a22 = _quadrants_b(A)
    b11, b12, b21, b22 = _quadrants_b(B)
    s1 = a12 + a22
    s2 = s1 - a11
    s3 = a11 - a12
    s4 = a21 - s2
    t1 = b12 - b11
    t2 = b22 - t1
    t3 = b22 - b12
    t4 = t2 - b21
    ea = _stack7([a11, a21, s4, a22, s1, s2, s3])
    eb = _stack7([b11, b21, b22, t4, t1, t2, t3])
    return ea, eb


def _cat_quads(c11, c12, c21, c22):
    top = jnp.concatenate([c11, c12], axis=2)
    bot = jnp.concatenate([c21, c22], axis=2)
    return jnp.concatenate([top, bot], axis=1)


def _decode_strassen(P):
    """One decode level: (7S, R, C, ...) products → (S, 2R, 2C, ...)."""
    P = P.reshape(P.shape[0] // 7, 7, *P.shape[1:])
    m1, m2, m3, m4, m5, m6, m7 = (P[:, t] for t in range(7))
    # same balanced association as `_rec_strassen` (bitwise equality)
    c11 = (m1 + m4) + (m7 - m5)
    c12 = m3 + m5
    c21 = m2 + m4
    c22 = (m1 - m2) + (m3 + m6)
    return _cat_quads(c11, c12, c21, c22)


def _decode_winograd(P):
    P = P.reshape(P.shape[0] // 7, 7, *P.shape[1:])
    p1, p2, p3, p4, p5, p6, p7 = (P[:, t] for t in range(7))
    u2 = p1 + p6
    u3 = u2 + p7
    u4 = u2 + p5
    c11 = p1 + p2
    c12 = u4 + p3
    c21 = u3 - p4
    c22 = u3 + p5
    return _cat_quads(c11, c12, c21, c22)


def _encode_fns(variant):
    if variant == "strassen":
        return _encode_strassen, _decode_strassen
    return _encode_winograd, _decode_winograd


def _leaf_dot(base_dot, A, B):
    """Dispatch a whole leaf stack as ONE batched TN product.

    ``(S, *batch, m, n) × (S, *batch, m, k)`` is flattened to a single
    leading dim for the base dot — the Pallas kernels take exactly one batch
    grid dimension (`repro.kernels` batched-grid contract) and the jnp base
    handles any leading dims — then unflattened.
    """
    S = A.shape[0]
    batch = A.shape[1:-2]
    out = base_dot(
        A.reshape(-1, *A.shape[-2:]), B.reshape(-1, *B.shape[-2:])
    )
    return out.reshape(S, *batch, *out.shape[-2:])


def _strassen_batched(a, b, L, base_dot, variant):
    """Iterative, level-synchronous Strassen: one root blocking transpose,
    encode L levels, one batched leaf dot, decode L levels, unblock.
    Operands arrive root-padded (2^L-divisible)."""
    if L == 0:
        return base_dot(a, b)
    enc, dec = _encode_fns(variant)
    A, B = _to_blocks(a, L)[None], _to_blocks(b, L)[None]
    for lev in range(1, L + 1):
        with obs.span("strassen.encode", level=lev):
            A, B = enc(A, B)
    # stacks are now (7^L, 1, 1, *batch, mb, nb): the block grid collapsed
    # into the leaf batch — squeeze it into the base dot's layout for free.
    with obs.span("strassen.leaf_dot", leaves=A.shape[0]):
        P = _leaf_dot(base_dot, A[:, 0, 0], B[:, 0, 0])
    P = P[:, None, None]
    for lev in range(L, 0, -1):
        with obs.span("strassen.decode", level=lev):
            P = dec(P)
    return _unblock(P)[0]


# ---------------------------------------------------------------------------
# fused leaf dispatch: per-leaf ±1 coefficient tables, zero operand stacks
#
# The batched dispatch materializes every encode level as a (7^ℓ, …) stack
# that the next level re-reads — the 2.0-words/add traffic the cost model
# charges it for. The fused dispatch never materializes an operand
# combination: each of the 7^L leaf operands is described by a *slot table*
# of 2^L (row, col, sign) entries over the root leaf-block grid
# (`_to_blocks` coordinates), built at trace time by mirroring
# `_encode_strassen` symbolically:
#
#   * two-term combination  x + σ·y  → concat slots(x) ++ σ·slots(y)
#   * single-term copy      x        → concat slots(x) ++ zero slots
#
# so slot k of a leaf operand is the coefficient of root block
# (rows[k], cols[k]) and the *position* of k encodes where that block sits
# in the unrolled recursion's add tree: evaluating the slots as a perfect
# binary tree (level-1 adds innermost, level-L outermost; zero slots drop
# out symbolically at trace time) reproduces the unrolled operand
# combinations bitwise — x−y ≡ x+(−y) and −(x+y) ≡ (−x)+(−y) are IEEE-754
# identities, and the quadrant slicing commutes with the elementwise adds.
#
# The tables are tiny (7^L · 2^L · 3 ints per operand side) and static, so
# they ride into the Pallas kernels as scalar-prefetch operands (the
# coefficient-table contract in `repro.kernels`); the XLA fallback gathers
# the blocks as plain slices of the original operand — no block-major
# transpose is ever materialized on that path. Only the classical variant
# has the one-add-per-level structure the slot encoding needs: Winograd's
# chained within-level combinations (s2 = s1 − a11, …) would square the
# table width per level, so `leaf_dispatch='fused'` requires
# `variant='strassen'`.
# ---------------------------------------------------------------------------

_FUSED_A_COMBOS = ((0, 3, 1), (1, 3, 1), (0, None, 0), (3, None, 0),
                   (0, 2, 1), (1, 0, -1), (2, 3, -1))
_FUSED_B_COMBOS = ((0, 3, 1), (0, None, 0), (1, 3, -1), (2, 0, -1),
                   (3, None, 0), (0, 1, 1), (2, 3, 1))


@functools.lru_cache(maxsize=None)
def _slot_tables(L: int):
    """Per-leaf ±1 coefficient tables of the fused dispatch.

    Returns ``((a_rows, a_cols, a_sgn), (b_rows, b_cols, b_sgn))`` — six
    ``(7**L, 2**L)`` int32 arrays. Row ``s`` describes leaf product ``s``
    (same leaf ordering as ``_stack7``: level-1 digit is the most
    significant base-7 digit); sign 0 marks a dead slot.
    """

    def build(combos):
        R = 1 << L
        r, c = np.indices((R, R))
        # (S, rows, cols, slots, {row, col, sign}) — starts as the identity
        slots = np.stack([r, c, np.ones((R, R), np.int64)], axis=-1)
        slots = slots[None, :, :, None, :]
        for _ in range(L):
            S, Rg, Cg, W, _ = slots.shape
            h, w = Rg // 2, Cg // 2
            quad = (slots[:, :h, :w], slots[:, :h, w:],
                    slots[:, h:, :w], slots[:, h:, w:])
            parts = []
            for p, q, sg in combos:
                first = quad[p]
                if q is None:
                    second = np.zeros_like(first)
                else:
                    second = quad[q].copy()
                    second[..., 2] *= sg
                parts.append(np.concatenate([first, second], axis=3))
            slots = np.stack(parts, axis=1).reshape(S * 7, h, w, 2 * W, 3)
        slots = slots[:, 0, 0]
        return (slots[..., 0].astype(np.int32),
                slots[..., 1].astype(np.int32),
                slots[..., 2].astype(np.int32))

    return build(_FUSED_A_COMBOS), build(_FUSED_B_COMBOS)


def _combine_slots(get_block, rows, cols, sgn):
    """One leaf operand from its slot table: the perfect binary add tree of
    the unrolled recursion. ``get_block(r, c)`` fetches root leaf block
    (r, c); dead (sign-0) slots drop out at trace time, so the jaxpr holds
    exactly the adds the unrolled recursion performs on this operand."""

    def ev(lo, hi):
        if hi - lo == 1:
            s = int(sgn[lo])
            if s == 0:
                return None
            blk = get_block(int(rows[lo]), int(cols[lo]))
            return -blk if s < 0 else blk
        mid = (lo + hi) // 2
        left, right = ev(lo, mid), ev(mid, hi)
        if left is None:
            return right
        if right is None:
            return left
        return left + right

    return ev(0, len(sgn))


def _block_getter(x, L):
    """Leaf-block fetcher in `_to_blocks` coordinates, as direct slices of
    the unblocked operand — the XLA fused path never materializes the
    block-major transpose."""
    mb, nb = x.shape[-2] >> L, x.shape[-1] >> L

    def get(r, c):
        return x[..., r * mb:(r + 1) * mb, c * nb:(c + 1) * nb]

    return get


def _strassen_fused(a, b, L, base_dot, fused_dot=None):
    """Fused-operand Strassen: slot-table gather+combine per leaf, one leaf
    launch, shared balanced decode. Operands arrive root-padded.

    With ``fused_dot`` (the Pallas fused kernel, `kernels.ops.gemm_tn_fused`)
    the gather+combine runs in the kernel prologue against the block-major
    layout; otherwise the combinations are built as trace-time slice
    gathers and the leaf stack feeds one batched ``base_dot``.
    """
    if L == 0:
        return base_dot(a, b)
    (ar, ac, asg), (br, bc, bsg) = _slot_tables(L)
    with obs.span("strassen.fused_leaves", leaves=7 ** L,
                  kernel=fused_dot is not None):
        if fused_dot is not None:
            # the Pallas fused launch: gather+combine happens in the kernel
            # prologue against the block-major layout (one leading group here)
            P = fused_dot(_to_blocks(a, L)[None], _to_blocks(b, L)[None],
                          _slot_tables(L))
        else:
            # XLA fallback: per-leaf combine + per-leaf dot. Stacking the
            # combined operands for one batched dot would just rebuild the
            # operand stack the fused dispatch exists to avoid (and XLA:CPU
            # runs a leading batch dim slower than the same dots unbatched);
            # only the product stack — the decode input — is materialized.
            ga, gb = _block_getter(a, L), _block_getter(b, L)
            P = jnp.stack([
                base_dot(_combine_slots(ga, ar[s], ac[s], asg[s]),
                         _combine_slots(gb, br[s], bc[s], bsg[s]))
                for s in range(7 ** L)
            ])
    P = P[:, None, None]
    for lev in range(L, 0, -1):
        with obs.span("strassen.decode", level=lev):
            P = _decode_strassen(P)
    return _unblock(P)[0]


def strassen_tn(
    a: jax.Array,
    b: jax.Array,
    *,
    alpha: float = 1.0,
    c: Optional[jax.Array] = None,
    beta: float = 1.0,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    base_dot: Optional[Callable] = None,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """``C = alpha·AᵀB (+ beta·C)`` via rectangular TN Strassen.

    Args:
      a: ``(m, n)`` left operand (used transposed, never materialized as Aᵀ).
        Leading batch dims are allowed if ``b`` carries matching ones (the
        recursion and base dot then run batched — one trace, no vmap).
      b: ``(m, k)`` right operand.
      alpha, c, beta: optional scaling/accumulation, BLAS-style.
      plan: a frozen :class:`repro.tune.Plan` carrying every tunable. With
        no plan and no pinned tunables, the dispatch is planned through
        ``repro.tune.plan`` (analytic cost model / plan cache).
      n_base: recursion cutoff — any dim ≤ n_base goes to the base matmul.
        Pinning this (or ``variant``) manually bypasses the planner.
      variant: ``'strassen'`` (paper-faithful) or ``'winograd'`` (15 adds).
      leaf_dispatch: ``'unrolled'`` (one dot per leaf, legacy),
        ``'batched'`` (level-synchronous: every leaf of the tree in one
        batched TN dot — bitwise-equal output, O(levels) jaxpr), or
        ``'fused'`` (per-leaf ±1 coefficient tables folded into the leaf
        launch — zero materialized operand-add stacks; classical variant
        only). Defaults to the plan's choice; does not bypass the planner
        when pinned alone (it never changes values).
      base_dot: base-case TN matmul ``f(a, b) -> aᵀb``. Defaults to a TN
        ``dot_general`` (MXU-native; the plan may swap in the Pallas
        ``gemm_tn`` kernel). Pass ``repro.kernels.ops.gemm_tn`` explicitly
        to force the kernel. Must accept one leading batch dim (it receives
        the whole leaf stack when ``leaf_dispatch='batched'``).
      acc_dtype: accumulation dtype for the base matmul
        (``preferred_element_type``).

    Returns:
      ``(n, k)`` product in ``acc_dtype`` (or the base_dot's output dtype).
    """
    if a.ndim < 2 or b.ndim < 2 or a.ndim != b.ndim:
        raise ValueError(f"strassen_tn expects 2-D+ operands, got {a.shape}, {b.shape}")
    if a.shape[-2] != b.shape[-2] or a.shape[:-2] != b.shape[:-2]:
        raise ValueError(
            f"contracting/batch dims mismatch: A is {a.shape}, B is {b.shape} "
            "(TN product contracts dim -2 of both; leading dims are batch)"
        )
    plan, n_base, variant, _, leaf_dispatch = resolve_tunables(
        plan, n_base, variant, None,
        op="gemm_tn", m=a.shape[-2], n=a.shape[-1], k=b.shape[-1],
        batch=math.prod(a.shape[:-2]) if a.ndim > 2 else 0,
        dtype=str(a.dtype), leaf_dispatch=leaf_dispatch,
    )
    if variant not in ("strassen", "winograd"):
        raise ValueError(f"unknown variant {variant!r}")
    if leaf_dispatch == "fused" and variant != "strassen":
        raise ValueError(
            "leaf_dispatch='fused' supports variant='strassen' only: "
            "Winograd's chained within-level combinations do not fit the "
            "per-leaf ±1 slot tables (see DESIGN.md §2)"
        )
    fused_dot = None
    if base_dot is None:
        _, base_dot = _plan_base_fns(plan, None, base_dot)
        if leaf_dispatch == "fused":
            _, fused_dot = _plan_fused_fns(plan)
    if base_dot is None:
        base_dot = functools.partial(_dot_tn, acc_dtype=acc_dtype)

    m, n = a.shape[-2:]
    k = b.shape[-1]
    L = tree_depth((m, n, k), n_base)
    obs.metrics.inc(f"dispatch.gemm_tn.{leaf_dispatch}")
    obs.metrics.inc("gemm_tn.leaves", 7 ** L)
    with obs.span(
        "strassen_tn", m=m, n=n, k=k, levels=L, leaf_dispatch=leaf_dispatch
    ):
        if L:
            # satellite of the batched-leaf PR: ONE root pad to 2^L multiples
            # (and one crop below) replaces the per-level _pad_even of the seed.
            a = _pad_root(a, L)
            b = _pad_root(b, L)
        if leaf_dispatch == "batched":
            out = _strassen_batched(a, b, L, base_dot, variant)
        elif leaf_dispatch == "fused":
            out = _strassen_fused(a, b, L, base_dot, fused_dot)
        else:
            rec = _rec_strassen if variant == "strassen" else _rec_winograd
            out = rec(a, b, n_base=n_base, base_dot=base_dot, acc_dtype=acc_dtype)
        out = out[..., :n, :k]
        if alpha != 1.0:
            out = alpha * out
        if c is not None:
            out = out + (beta * c if beta != 1.0 else c)
        return out
