"""ATA — cache-oblivious Strassen-based ``C = alpha·AᵀA`` (paper Algorithm 1).

The recursion (Eq. 1-2 of the paper), for ``A ∈ R^{m×n}`` split into 2×2
quadrants:

    C11 = A11ᵀA11 + A21ᵀA21      (two recursive ATA calls)
    C22 = A12ᵀA12 + A22ᵀA22      (two recursive ATA calls)
    C21 = A12ᵀA11 + A22ᵀA21      (two rectangular Strassen TN calls)
    C12 = C21ᵀ                   (never computed — symmetry)

Cost: ``T(n) = 4T(n/2) + 2T_S(n/2) + 3(n/2)² ≈ (2/3)·T_S(n)`` — two thirds of
Strassen applied naively, i.e. (14/3)·n^{log₂7} (paper Section 3.2).

TPU adaptation notes (see DESIGN.md §2):

* the recursion unrolls at trace time (static shapes) — cache-obliviousness
  survives as nested recursive blocking that XLA/Mosaic tiles onto
  HBM→VMEM→VREG;
* odd shapes are handled by **one root pad** (to a shape divisible by
  ``2^L`` for the recursion depth ``L``) and a crop-aware root assembly —
  no per-level padding, every interior split is an exact half;
* the symmetric saving at the *base-case* level lives in the Pallas ``syrk``
  kernel, which computes only lower-triangular output blocks;
* **the symmetric saving at the storage level lives here**: the recursion is
  organized as a *slab sum* — each node computes ``Σ_k A_kᵀA_k`` over a list
  of row-slabs for one contiguous column range — and returns a
  ``(c11, c21, c22)`` triangular node structure instead of a dense square.
  No ``jnp.block`` and no ``C21ᵀ`` is materialized at any intermediate level;
  the lower triangle is assembled exactly once at the root (each block
  written once via static-offset updates), and the mirror to a full square
  happens once for dense output — or never, when the caller asks for packed
  output via ``ata(a, out="packed")``, which returns a
  :class:`repro.core.symmetric.SymmetricMatrix`;
* **leaf dispatch** is pluggable (``Plan.leaf_dispatch``): the legacy
  ``'unrolled'`` recursion emits ``4^L`` base syrks and ``O(7^L)`` Strassen
  leaf dots as separate ops; ``'batched'`` runs the same tree
  level-synchronously — all diagonal leaves as ONE batched syrk and every
  Strassen leaf of every off-diagonal block as ONE batched TN dot — and
  decodes back into the identical ``_TriNode`` assembly, bitwise-equal to
  the unrolled form (tested; see DESIGN.md §2).

``ata`` is a pure JAX function: it composes with ``jit``, ``vmap``, ``grad``,
and ``shard_map`` (used by ``repro.core.distributed``). ``ata_batched`` runs
the same recursion with an explicit leading batch dimension — one trace, one
kernel launch per base tile over the whole batch — which is what the
blocked-Shampoo optimizer uses for its per-block gram statistics.

Dispatch tunables (cutoff, variant, kernel blocks, packed block, leaf
dispatch) resolve through the ``repro.tune`` planning layer: pass a frozen
``plan=``, pin values manually, or pass nothing and let the front door
decide (see DESIGN.md §7).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.strassen import (
    DEFAULT_N_BASE,
    _dot_tn,
    _encode_fns,
    _leaf_dot,
    _pad_root,
    _plan_base_fns,
    _rec_strassen,
    _rec_winograd,
    _to_blocks,
    _unblock,
    resolve_tunables,
    tree_depth,
)
from repro.core.symmetric import (
    SymmetricMatrix,
    default_block_size,
    sym_tile,
    write_packed_region,
)
from repro.tune.defaults import DEFAULT_PACKED_BLOCK  # re-export

__all__ = ["ata", "ata_batched", "DEFAULT_N_BASE", "DEFAULT_PACKED_BLOCK"]


def _syrk_base(a, acc_dtype):
    """Default base case: ``AᵀA`` via one TN dot, lower triangle mirrored.

    The Pallas kernel (``repro.kernels.ops.syrk``) replaces this on TPU and
    computes only the lower-triangular blocks; at the pure-jnp level the MXU
    executes the full tile matmul, and we mirror ``low(C)`` so the tile-level
    invariant *the base tile is exactly symmetric* holds bitwise (XLA's
    accumulation order can differ per output position, so the raw matmul is
    only approximately symmetric). This transpose is a ≤ n_base tile op — the
    full-square mirror of the seed implementation is gone.
    """
    return sym_tile(_dot_tn(a, a, acc_dtype))


class _TriNode(NamedTuple):
    """One recursion level of the symmetric product: C = [[c11, ·], [c21, c22]].

    ``c11``/``c22`` are ``_TriNode`` or dense symmetric base tiles; ``c21`` is
    the dense rectangular off-diagonal block. The never-computed upper block
    has no representation — that is the point.
    """

    c11: object
    c21: jax.Array
    c22: object


def _rec_ata(slabs, n_base, base_syrk, strassen_rec, base_dot, acc_dtype):
    """Compute ``Σ_k slab_kᵀ·slab_k`` for one column range, as a _TriNode tree.

    ``slabs`` is a list of ``(..., m_k, n)`` row-slabs sharing the column
    range (the paper's ``C11 = A11ᵀA11 + A21ᵀA21`` generalized: every level
    of row-halving doubles the slab list instead of materializing partial
    dense sums). Keeping the sum *inside* the recursion means both addends of
    every accumulation share one node structure by construction — the result
    tree is a function of the column range only. Inputs arrive root-padded,
    so every split below is an exact half.
    """
    n = slabs[0].shape[-1]
    m_max = max(s.shape[-2] for s in slabs)
    if n <= n_base or m_max <= n_base:
        with obs.span("ata.rec.base", n=n, slabs=len(slabs)):
            out = base_syrk(slabs[0])
            for s in slabs[1:]:
                p = base_syrk(s)
                with obs.span("ata.slab_sum"):
                    out = out + p
            return out

    halves = []
    for s in slabs:
        m1 = s.shape[-2] // 2
        if m1:
            halves.append(s[..., :m1, :])
        halves.append(s[..., m1:, :])
    n1 = n // 2
    left = [h[..., :n1] for h in halves]
    right = [h[..., n1:] for h in halves]

    rec = functools.partial(
        _rec_ata,
        n_base=n_base,
        base_syrk=base_syrk,
        strassen_rec=strassen_rec,
        base_dot=base_dot,
        acc_dtype=acc_dtype,
    )
    st = functools.partial(
        strassen_rec, n_base=n_base, base_dot=base_dot, acc_dtype=acc_dtype
    )

    with obs.span("ata.rec", n=n, slabs=len(slabs)):
        c11 = rec(left)
        c22 = rec(right)
        c21 = st(right[0], left[0])
        for r, l in zip(right[1:], left[1:]):
            p = st(r, l)
            with obs.span("ata.slab_sum"):
                c21 = c21 + p
        return _TriNode(c11, c21, c22)


# ---------------------------------------------------------------------------
# level-synchronous batched-leaf formulation of the same tree
# ---------------------------------------------------------------------------


def _accum_axis1(x):
    """Left-to-right accumulation over axis 1 — the exact add order of the
    unrolled slab loop (``out = t0; out = out + t1; …``), on a stack."""
    acc = x[:, 0]
    for r in range(1, x.shape[1]):
        acc = acc + x[:, r]
    return acc


def _ata_level_sync(a, L, *, variant, base_syrk, base_dot):
    """The whole ATA tree with batched leaves: encode every off-diagonal
    Strassen product into per-level stacks, run ALL ``Σ_ℓ 2^{2ℓ-1}·7^{L-ℓ}``
    Strassen leaves as one batched TN dot and ALL ``4^L`` diagonal leaves as
    one batched syrk, then decode back into the identical ``_TriNode`` tree.

    ``a`` arrives root-padded: ``(*batch, M, N)`` with both dims divisible
    by ``2^L``; it is transposed ONCE into the leaf-block-major layout of
    ``core.strassen`` (``(R, C, *batch, mL, nL)``), from which every group's
    operands are leading-axis block slices. An ATA-level-ℓ group is ordered
    ``s = i·2^ℓ + r`` (``i`` = parent column range, ``r`` = row slab), so
    the per-``i`` slab accumulation of the unrolled recursion is a
    left-to-right fold over a reshaped axis.
    """
    if L == 0:
        return base_syrk(a)
    batch = a.shape[:-2]
    enc, dec = _encode_fns(variant)
    R = 1 << L
    ab = _to_blocks(a, L)           # (R, R, *batch, mL, nL)
    mL, nL = ab.shape[-2:]

    # encode: one Strassen operand stack per ATA level ℓ (the C21 blocks of
    # the 2^{ℓ-1} nodes split at level ℓ-1, × 2^ℓ row slabs each), pushed
    # down the remaining L-ℓ Strassen levels, then concatenated into ONE
    # leaf stack across all levels (every leaf has the same (mL, nL) shape).
    parts_a, parts_b, sizes = [], [], []
    for lev in range(1, L + 1):
      with obs.span("ata.encode", level=lev):
        Rl, H = 1 << lev, 1 << (lev - 1)
        q = R // Rl
        # block rows grouped into the 2^ℓ slabs, block columns into
        # (parent i, left/right, q): operand (i, r) is a pure block slice
        g = ab.reshape(Rl, q, H, 2, q, *batch, mL, nL)
        right = jnp.moveaxis(g[:, :, :, 1], 2, 0)   # (H, Rl, q, q, ...)
        left = jnp.moveaxis(g[:, :, :, 0], 2, 0)
        A = right.reshape(H * Rl, q, q, *batch, mL, nL)
        B = left.reshape(H * Rl, q, q, *batch, mL, nL)
        for _ in range(L - lev):
            A, B = enc(A, B)
        parts_a.append(A[:, 0, 0])  # grids collapsed to (1, 1): squeeze
        parts_b.append(B[:, 0, 0])
        sizes.append(A.shape[0])
    with obs.span("ata.leaf_dot", leaves=sum(sizes)):
        P = _leaf_dot(
            base_dot,
            jnp.concatenate(parts_a, axis=0),
            jnp.concatenate(parts_b, axis=0),
        )
    P_levels = []
    off = 0
    for size in sizes:
        P_levels.append(P[off : off + size])
        off += size

    # all diagonal leaves as one batched syrk, ordered (column block i, slab r)
    with obs.span("ata.syrk_batch", leaves=R * R):
        D = jnp.swapaxes(ab, 0, 1).reshape(R * R, *batch, mL, nL)
        Dp = base_syrk(D.reshape(-1, mL, nL))
    Dp = Dp.reshape(R, R, *batch, *Dp.shape[-2:])
    diag = _accum_axis1(Dp)  # (2^L, *batch, nL, nL)

    # decode: per level, pop its slice of the leaf stack, fold the Strassen
    # levels back up, fold the slab sum in block form, then unblock
    c21 = {}
    for lev, p in zip(range(1, L + 1), P_levels):
      with obs.span("ata.decode", level=lev):
        p = p[:, None, None]
        for _ in range(L - lev):
            p = dec(p)
        Rl, Hl = 1 << lev, 1 << (lev - 1)
        q = R // Rl
        p = _accum_axis1(p.reshape(Hl, Rl, q, q, *p.shape[3:]))
        c21[lev] = _unblock(p)      # (H, *batch, N/2^ℓ, N/2^ℓ)

    def build(lev, idx):
        if lev == L:
            return diag[idx]
        return _TriNode(
            build(lev + 1, 2 * idx), c21[lev + 1][idx], build(lev + 1, 2 * idx + 1)
        )

    return build(0, 0)


# ---------------------------------------------------------------------------
# root assembly (crop-aware: the node tree covers the padded N ≥ n)
# ---------------------------------------------------------------------------


def _first_leaf(node):
    while isinstance(node, _TriNode):
        node = node.c11
    return node


def _assemble_lower(node, buf, off, lim):
    """Write the lower-triangular content of ``node`` into ``buf`` at diagonal
    offset ``off``, clipped to ``lim`` (the true n — blocks can overhang into
    the root pad). Each surviving piece is written exactly once
    (static-offset updates); no concatenation, no transposes."""
    if not isinstance(node, _TriNode):
        h = min(node.shape[-1], lim - off)
        if h <= 0:
            return buf
        return buf.at[..., off : off + h, off : off + h].set(node[..., :h, :h])
    n1 = node.c21.shape[-1]
    m2 = node.c21.shape[-2]
    buf = _assemble_lower(node.c11, buf, off, lim)
    r0 = off + n1
    h, w = min(m2, lim - r0), min(n1, lim - off)
    if h > 0 and w > 0:
        buf = buf.at[..., r0 : r0 + h, off : off + w].set(node.c21[..., :h, :w])
    return _assemble_lower(node.c22, buf, off + n1, lim)


def _lower_dense(node, n):
    """Assemble the root lower triangle (strictly-upper block region zero,
    diagonal base tiles full-symmetric)."""
    leaf = _first_leaf(node)
    batch = leaf.shape[:-2]
    buf = jnp.zeros((*batch, n, n), leaf.dtype)
    return _assemble_lower(node, buf, 0, n)


def _finalize_dense(node, n):
    if not isinstance(node, _TriNode):
        return node  # single base tile: already full and bitwise symmetric
    # the one and only full-square mirror — at the root, for dense consumers.
    return sym_tile(_lower_dense(node, n))


def _assemble_packed(node, buf, off, bn, lim):
    # write_packed_region (core.symmetric): each block lands in packed
    # storage via static-offset updates, strictly-upper pieces skipped;
    # blocks overhanging ``lim`` (the packed grid extent) are clipped.
    if not isinstance(node, _TriNode):
        h = min(node.shape[-1], lim - off)
        if h <= 0:
            return buf
        return write_packed_region(buf, node[..., :h, :h], off, off, bn)
    n1 = node.c21.shape[-1]
    m2 = node.c21.shape[-2]
    buf = _assemble_packed(node.c11, buf, off, bn, lim)
    r0 = off + n1
    h, w = min(m2, lim - r0), min(n1, lim - off)
    if h > 0 and w > 0:
        buf = write_packed_region(buf, node.c21[..., :h, :w], r0, off, bn)
    return _assemble_packed(node.c22, buf, off + n1, bn, lim)


def _finalize_packed(node, n, packed_block):
    """Pack the node tree directly — the dense square is never materialized
    (each result block is written once, straight into packed storage)."""
    bn = default_block_size(n, packed_block)
    nb = -(-n // bn)
    leaf = _first_leaf(node)
    batch = leaf.shape[:-2]
    buf = jnp.zeros((*batch, nb * (nb + 1) // 2, bn, bn), leaf.dtype)
    return SymmetricMatrix(_assemble_packed(node, buf, 0, bn, nb * bn), n, bn)


def _ata_impl(
    a,
    *,
    alpha,
    c,
    beta,
    plan,
    n_base,
    variant,
    leaf_dispatch,
    base_syrk,
    base_dot,
    acc_dtype,
    out,
    packed_block,
):
    if out not in ("dense", "packed"):
        raise ValueError(f"unknown output mode {out!r}; use 'dense' or 'packed'")
    plan, n_base, variant, packed_block, leaf_dispatch = resolve_tunables(
        plan, n_base, variant, packed_block,
        op="ata", m=a.shape[-2], n=a.shape[-1],
        batch=a.shape[0] if a.ndim > 2 else 0,
        dtype=str(a.dtype), out=out, leaf_dispatch=leaf_dispatch,
    )
    if variant not in ("strassen", "winograd"):
        raise ValueError(f"unknown variant {variant!r}")
    base_syrk, base_dot = _plan_base_fns(plan, base_syrk, base_dot)
    if base_syrk is None:
        base_syrk = functools.partial(_syrk_base, acc_dtype=acc_dtype)
    if base_dot is None:
        base_dot = functools.partial(_dot_tn, acc_dtype=acc_dtype)

    n = a.shape[-1]
    L = tree_depth(a.shape[-2:], n_base)
    obs.metrics.inc(f"dispatch.ata.{leaf_dispatch}")
    # leaf accounting, identical across the two dispatches (the tree is a
    # function of L only): 4^L diagonal syrk leaves, Σ_ℓ 2^{2ℓ-1}·7^{L-ℓ}
    # off-diagonal Strassen leaves — what cost.dispatch_calls predicts.
    obs.metrics.inc("ata.leaves.syrk", 4 ** L)
    obs.metrics.inc(
        "ata.leaves.strassen",
        sum(2 ** (2 * lev - 1) * 7 ** (L - lev) for lev in range(1, L + 1)),
    )
    with obs.span(
        "ata", m=a.shape[-2], n=n, levels=L, leaf_dispatch=leaf_dispatch
    ):
        with obs.span("ata.pad"):
            ap = _pad_root(a, L) if L else a
        if leaf_dispatch == "batched":
            node = _ata_level_sync(
                ap, L, variant=variant, base_syrk=base_syrk, base_dot=base_dot,
            )
        else:
            strassen_rec = _rec_strassen if variant == "strassen" else _rec_winograd
            node = _rec_ata(
                [ap],
                n_base=n_base,
                base_syrk=base_syrk,
                strassen_rec=strassen_rec,
                base_dot=base_dot,
                acc_dtype=acc_dtype,
            )

        if out == "packed":
            with obs.span("ata.pack"):
                result = _finalize_packed(node, n, packed_block)
            if alpha != 1.0:
                result = result.scale(alpha)
            if c is not None:
                if not isinstance(c, SymmetricMatrix):
                    raise TypeError(
                        "ata(..., out='packed') accumulates only into a "
                        f"SymmetricMatrix c, got {type(c).__name__}"
                    )
                result = result.add(c.scale(beta) if beta != 1.0 else c)
            return result

        result = _finalize_dense(node, n)
        if alpha != 1.0:
            result = alpha * result
        if c is not None:
            if isinstance(c, SymmetricMatrix):
                c = c.to_dense()
            result = result + (beta * c if beta != 1.0 else c)
        return result


def ata(
    a: jax.Array,
    *,
    alpha: float = 1.0,
    c: Optional[Union[jax.Array, SymmetricMatrix]] = None,
    beta: float = 1.0,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    base_syrk: Optional[Callable] = None,
    base_dot: Optional[Callable] = None,
    acc_dtype=jnp.float32,
    out: str = "dense",
    packed_block: Optional[int] = None,
) -> Union[jax.Array, SymmetricMatrix]:
    """``C = alpha·AᵀA (+ beta·C)`` via the paper's ATA algorithm.

    Args:
      a: ``(m, n)`` input, any rectangular shape (odd sizes handled by one
        root pad to a ``2^L``-divisible shape and a crop-aware assembly).
      alpha, c, beta: BLAS-style scaling/accumulation. With ``out='packed'``,
        ``c`` must itself be a ``SymmetricMatrix`` of matching layout.
      plan: a frozen :class:`repro.tune.Plan` carrying every tunable
        (cutoff, variant, kernel blocks, packed block, leaf dispatch). With
        no plan and no pinned tunables the dispatch is planned through
        ``repro.tune.plan`` — the analytic cost model, or a measured plan
        from the cache. Note the output *type* always follows ``out``,
        never the plan.
      n_base: recursion cutoff; tiles with any dim ≤ n_base go to the base
        syrk/gemm. The TPU analogue of the paper's "fits in cache".
        Pinning this (or ``variant``) manually bypasses the planner and
        fills the rest from ``repro.tune.defaults``.
      variant: Strassen variant for the C21 off-diagonal products —
        ``'strassen'`` (paper-faithful) or ``'winograd'`` (beyond-paper,
        15 adds).
      leaf_dispatch: ``'unrolled'`` (one op per leaf) or ``'batched'``
        (level-synchronous: ONE batched syrk for all diagonal leaves + ONE
        batched TN dot for every Strassen leaf — bitwise-equal result,
        O(levels) jaxpr). Defaults to the plan's choice; pinning it alone
        does not bypass the planner (it never changes values).
      base_syrk: base-case ``f(a) -> aᵀa`` (full, bitwise-symmetric tile).
        Defaults to a TN dot_general (or the plan's Pallas kernel); pass
        ``repro.kernels.ops.syrk`` to force the kernel. Must accept one
        leading batch dim (it receives the whole diagonal-leaf stack when
        ``leaf_dispatch='batched'``).
      base_dot: base-case ``f(a, b) -> aᵀb`` for the Strassen leaves (same
        leading-batch contract).
      acc_dtype: accumulation dtype.
      out: ``'dense'`` → ``(n, n)`` full symmetric array (one mirror, at the
        root). ``'packed'`` → :class:`SymmetricMatrix` holding only the
        ``nb(nb+1)/2`` lower-triangular blocks — no mirror anywhere.
      packed_block: block size of the packed output grid (clamped to the
        matrix size; see ``symmetric.default_block_size``).

    Returns:
      ``(n, n)`` full symmetric product, or its packed form.
    """
    if a.ndim != 2:
        raise ValueError(f"ata expects a 2-D operand, got shape {a.shape}")
    return _ata_impl(
        a,
        alpha=alpha,
        c=c,
        beta=beta,
        plan=plan,
        n_base=n_base,
        variant=variant,
        leaf_dispatch=leaf_dispatch,
        base_syrk=base_syrk,
        base_dot=base_dot,
        acc_dtype=acc_dtype,
        out=out,
        packed_block=packed_block,
    )


def ata_batched(
    a: jax.Array,
    *,
    alpha: float = 1.0,
    c: Optional[Union[jax.Array, SymmetricMatrix]] = None,
    beta: float = 1.0,
    plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
    leaf_dispatch: Optional[str] = None,
    base_syrk: Optional[Callable] = None,
    base_dot: Optional[Callable] = None,
    acc_dtype=jnp.float32,
    out: str = "dense",
    packed_block: Optional[int] = None,
) -> Union[jax.Array, SymmetricMatrix]:
    """Batched ``C_b = alpha·A_bᵀA_b`` for ``a: (B, m, n)`` — one trace.

    Unlike ``vmap(ata)``, the batch dimension is threaded through the
    recursion itself: every base case is a single *batched* syrk over all B
    tiles (one kernel launch with a leading batch grid dimension when the
    Pallas kernel is the base), and every Strassen leaf is a batched TN dot.
    With ``leaf_dispatch='batched'`` the leaf stack and the operand batch
    are flattened into that one leading kernel dim, so the whole gram batch
    still costs two launches total. ``out='packed'`` returns a
    ``SymmetricMatrix`` whose blocks carry the leading batch dim:
    ``(B, T, bn, bn)``. This is the gram-statistics entry point for the
    blocked-Shampoo optimizer.
    """
    if a.ndim != 3:
        raise ValueError(f"ata_batched expects a (B, m, n) operand, got {a.shape}")
    return _ata_impl(
        a,
        alpha=alpha,
        c=c,
        beta=beta,
        plan=plan,
        n_base=n_base,
        variant=variant,
        leaf_dispatch=leaf_dispatch,
        base_syrk=base_syrk,
        base_dot=base_dot,
        acc_dtype=acc_dtype,
        out=out,
        packed_block=packed_block,
    )
