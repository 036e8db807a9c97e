"""Blocked right-looking Cholesky on the packed lower-triangular block grid.

``cholesky`` factors an SPD :class:`repro.core.SymmetricMatrix` (or a dense
square, which is packed first by a pure gather) into a
:class:`CholeskyFactor` holding the *same* ``(..., T, bn, bn)`` packed
block pytree — the factorization walks the block grid in place and never
materializes a dense ``(n, n)`` anywhere:

    for block column j:                            (right-looking)
        S_jj   = A[j,j] − Σ_{k<j} L[j,k]·L[j,k]ᵀ   (one NT block einsum)
        L[j,j] = potrf(S_jj)                        (diagonal base kernel)
        S_ij   = A[i,j] − Σ_{k<j} L[i,k]·L[j,k]ᵀ   (one batched einsum)
        L[i,j] = S_ij · L[j,j]⁻ᵀ     for all i > j  (the panel step)

Base engines follow the plan like every other consumer of the stack:
``plan.use_kernels`` → the Pallas ``potrf``/``trsm`` kernels
(``repro.kernels``), whose leading batch grid dimension receives the whole
flattened batch stack per that package's batched-dispatch contract — a
batched Shampoo stat stack factors as ONE launch per block column per op.
There the panel step inverts the diagonal tile once per column,
``W = L[j,j]⁻ᵀ`` (one Pallas ``trsm`` of a single ``bn``-row tile against
the identity, per batch entry), and applies it to the whole sub-diagonal
panel in one einsum at the operands' ``dot_precision`` (``HIGHEST`` for
f32): the kernel's ``bn``-step recurrence runs once per column instead of
once per panel tile, and the panel's work goes through the MXU. Otherwise
the jnp/LAPACK-lowered base (``jnp.linalg.cholesky`` /
``lax.linalg.triangular_solve``) serves every backend, and the panel step stays one batched ``trsm`` over the
flattened ``batch × panel-rows`` stack (``L[j,j]`` broadcast to every panel
tile); explicit ``base_potrf``/``base_trsm`` engines take that route too.
On either engine the *walk* — and therefore the block arithmetic and its
float rounding — is the same for packed and dense inputs, which is what
makes them factor bitwise-identically (tested).

Padding: the packed grid covers ``nb·bn ≥ n``; the pad rows/cols of a gram
are zero, which would make the trailing diagonal block singular. The walk
masks the tail block's pad region to the identity before its ``potrf``, so
the factor is identity there and zero-padded right-hand sides solve to
zero-padded solutions — the crop at the end is exact.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.precision import dot_precision
from repro.core.symmetric import (
    SymmetricMatrix,
    default_block_size,
    diag_block_indices,
    sym_tile,
    tri_block_indices,
)

__all__ = ["CholeskyFactor", "cholesky"]


@jax.tree_util.register_pytree_node_class
class CholeskyFactor:
    """Lower-triangular Cholesky factor in packed block storage.

    Same geometry as :class:`SymmetricMatrix` — ``blocks: (..., T, bn, bn)``
    under the row-major lower enumeration ``t = i(i+1)/2 + j`` — but the
    content contract differs: diagonal tiles are **lower-triangular**
    (strict upper half zero) and there is no mirror anywhere; ``to_dense``
    assembles the lower-triangular ``L`` with zeros above the diagonal.
    Registered as a pytree, so factors ride through ``jit``/``lax.cond``
    and live directly in optimizer state (the packed-Shampoo p=2 path) and
    checkpoints (blocks + ``(n, bn)`` metadata — see DESIGN.md §5).
    """

    __slots__ = ("blocks", "n", "bn")

    def __init__(self, blocks, n: int, bn: int):
        self.blocks = blocks
        self.n = int(n)
        self.bn = int(bn)

    @property
    def nb(self) -> int:
        return -(-self.n // self.bn)

    @property
    def t_total(self) -> int:
        return self.nb * (self.nb + 1) // 2

    @property
    def shape(self):
        return tuple(self.blocks.shape[:-3]) + (self.n, self.n)

    @property
    def dtype(self):
        return self.blocks.dtype

    @property
    def nbytes(self) -> int:
        return int(self.blocks.size) * self.blocks.dtype.itemsize

    def tree_flatten(self):
        return (self.blocks,), (self.n, self.bn)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    @classmethod
    def identity(cls, n: int, bn: int, batch=(), dtype=jnp.float32):
        """The identity factor (L = I): the well-posed init value for
        factor-shaped optimizer state slots."""
        bn = default_block_size(n, bn)
        nb = -(-n // bn)
        t = nb * (nb + 1) // 2
        base = np.zeros((t, bn, bn), np.float32)
        base[diag_block_indices(nb)] = np.eye(bn, dtype=np.float32)
        blocks = jnp.broadcast_to(
            jnp.asarray(base, dtype), (*batch, t, bn, bn)
        )
        return cls(blocks, n, bn)

    def block(self, i: int, j: int):
        """The ``(..., bn, bn)`` factor tile at block position ``(i, j)``."""
        if j > i:
            raise ValueError(f"block ({i}, {j}) lies in the upper triangle")
        return self.blocks[..., i * (i + 1) // 2 + j, :, :]

    def to_dense(self):
        """Dense lower-triangular ``(..., n, n)`` L — conversion boundary
        only (tests/interop); the solvers never call this."""
        nb, bn, n = self.nb, self.bn, self.n
        i_idx, j_idx = tri_block_indices(nb)

        def unpack2d(blocks):
            z = jnp.zeros((nb, bn, nb, bn), blocks.dtype)
            z = z.at[i_idx, :, j_idx, :].set(blocks)
            return z.reshape(nb * bn, nb * bn)[:n, :n]

        fn = unpack2d
        for _ in self.blocks.shape[:-3]:
            fn = jax.vmap(fn)
        return fn(self.blocks)

    def __repr__(self):
        return (
            f"CholeskyFactor(n={self.n}, bn={self.bn}, "
            f"blocks={getattr(self.blocks, 'shape', None)}, "
            f"dtype={getattr(self.blocks, 'dtype', None)})"
        )


# ---------------------------------------------------------------------------
# base engines (the solver analogue of core.strassen._plan_base_fns)
# ---------------------------------------------------------------------------


def _flat_call(fn: Callable, *ops):
    """Call a base kernel on operands with arbitrary leading dims, flattened
    to the ONE leading batch dim of the ``repro.kernels`` batched-grid
    contract (2-D operands pass through unflattened)."""
    lead = ops[0].shape[:-2]
    if not lead:
        return fn(*ops)
    flat = [o.reshape(-1, *o.shape[-2:]) for o in ops]
    out = fn(*flat)
    return out.reshape(*lead, *out.shape[-2:])


def _potrf_jnp(s):
    return jnp.linalg.cholesky(s)


def _trsm_panel_jnp(l, p):
    # X·Lᵀ = P  (the factorization panel op), batched over leading dims
    return jax.lax.linalg.triangular_solve(
        l, p, left_side=False, lower=True, transpose_a=True
    )


def _uses_kernels(plan) -> bool:
    return plan is not None and getattr(plan, "use_kernels", False)


def base_solver_fns(plan):
    """(base_potrf, base_trsm) for the factor walk under this plan.

    ``use_kernels=True`` → the Pallas kernels (compiled on TPU, interpret
    elsewhere — ``kernels.ops`` decides); otherwise the jnp bases. Both
    accept one flattened leading batch dim (``_flat_call`` guarantees it).
    """
    if _uses_kernels(plan):
        from repro.kernels import ops

        return ops.potrf, functools.partial(ops.trsm, transpose=True)
    return _potrf_jnp, _trsm_panel_jnp


# ---------------------------------------------------------------------------
# the factor walk
# ---------------------------------------------------------------------------


def _pad_identity_mask(n: int, nb: int, bn: int):
    """(valid_2d, eye_pad) masks for the trailing diagonal block: zero the
    pad rows/cols, then place ones on the pad diagonal — the tail block
    factors as identity and zero-padded RHS stay zero."""
    d = n - (nb - 1) * bn  # valid extent of the last block, 1..bn
    valid = np.zeros((bn, bn), np.float32)
    valid[:d, :d] = 1.0
    eye_pad = np.zeros((bn, bn), np.float32)
    eye_pad[range(d, bn), range(d, bn)] = 1.0
    return jnp.asarray(valid), jnp.asarray(eye_pad)


def cholesky(
    a: Union[SymmetricMatrix, jax.Array],
    *,
    ridge: float = 0.0,
    plan=None,
    packed_block: Optional[int] = None,
    base_potrf: Optional[Callable] = None,
    base_trsm: Optional[Callable] = None,
) -> CholeskyFactor:
    """Packed blocked Cholesky: ``A = L·Lᵀ`` on the block grid, in place.

    Args:
      a: SPD :class:`SymmetricMatrix` (any leading batch dims on its
        blocks), or a dense ``(..., n, n)`` square — packed first via the
        pure-gather :meth:`SymmetricMatrix.from_dense`, after which the
        *identical* walk runs, so packed and dense inputs of equal values
        factor bitwise-identically.
      ridge: optional ``+ ridge·I`` on the logical diagonal before
        factoring (packed-native — only diagonal tiles touched).
      plan: a :class:`repro.tune.Plan` — supplies the packed block size
        (dense inputs) and the base-engine choice (``use_kernels``).
      packed_block: block size override when packing a dense input.
      base_potrf / base_trsm: explicit base engines (must accept one
        leading batch dim, per the ``repro.kernels`` contract); the panel
        step then solves every panel tile with ``base_trsm``.

    Returns:
      :class:`CholeskyFactor` with the same batch dims and block grid.
    """
    if not isinstance(a, SymmetricMatrix):
        if packed_block is None:
            packed_block = (
                plan.packed_block if plan is not None else None
            )
        if packed_block is None:
            from repro.tune.defaults import DEFAULT_PACKED_BLOCK

            packed_block = DEFAULT_PACKED_BLOCK
        a = SymmetricMatrix.from_dense(a, packed_block)
    if ridge:
        a = a.add_scaled_identity(ridge)
    # only the plan's kernel engine multiplies by inverted diagonal tiles
    panel_inverse = False
    if base_potrf is None and base_trsm is None:
        base_potrf, base_trsm = base_solver_fns(plan)
        panel_inverse = _uses_kernels(plan)
    elif base_potrf is None or base_trsm is None:
        raise ValueError("pass both base_potrf and base_trsm, or neither")

    nb, bn, n = a.nb, a.bn, a.n
    pad = nb * bn - n
    i_idx, j_idx = tri_block_indices(nb)
    tiles = {
        (int(i_idx[t]), int(j_idx[t])): a.block(int(i_idx[t]), int(j_idx[t]))
        for t in range(a.t_total)
    }

    out = {}
    for j in range(nb):
        s = tiles[(j, j)]
        if j:
            lrow = jnp.stack([out[(j, k)] for k in range(j)], axis=0)
            # pin the Schur accumulation width: einsum would otherwise
            # inherit the operand dtype (sub-f32 for a bf16 factor) —
            # the repro.check acc-dtype contract
            s = s - jnp.einsum(
                "k...ab,k...cb->...ac", lrow, lrow,
                precision=dot_precision(lrow),
                preferred_element_type=jnp.float32,
            )
        # the LOWER half of a packed diagonal tile is the authoritative
        # content (straddling producers may leave intra-tile upper corners
        # unwritten — to_dense's mirror reconstructs them); mirror it here
        # so every base engine (jnp.linalg.cholesky symmetrizes its input!)
        # sees the same full SPD tile.
        s = sym_tile(s)
        if pad and j == nb - 1:
            valid, eye_pad = _pad_identity_mask(n, nb, bn)
            s = s * valid + eye_pad
        out[(j, j)] = _flat_call(base_potrf, s)

        rows = range(j + 1, nb)
        if not rows:
            continue
        # the sub-diagonal panel of column j, leading-axis-major for the
        # batched-kernel contract (col_panel enumerates ascending i)
        p = jnp.moveaxis(a.col_panel(j), -3, 0)
        if j:
            li = jnp.stack(
                [jnp.stack([out[(i, k)] for k in range(j)], 0) for i in rows], 0
            )
            p = p - jnp.einsum(
                "rk...ab,k...cb->r...ac", li, lrow,
                precision=dot_precision(li, lrow),
                preferred_element_type=jnp.float32,
            )
        ljj = out[(j, j)]
        if panel_inverse:
            obs.metrics.inc("solve.cholesky.panel_inverse")
            # W·L[j,j]ᵀ = I  ⇒  W = L[j,j]⁻ᵀ, then L[i,j] = S_ij·W
            eye = jnp.broadcast_to(jnp.eye(bn, dtype=p.dtype), ljj.shape)
            w = _flat_call(base_trsm, ljj, eye)
            panel = jnp.einsum(
                "r...ab,...bc->r...ac", p, w,
                precision=dot_precision(p, w),
                preferred_element_type=jnp.float32,
            )
        else:
            panel = _flat_call(base_trsm, jnp.broadcast_to(ljj, p.shape), p)
        for r, i in enumerate(rows):
            out[(i, j)] = panel[r]

    blocks = jnp.stack(
        [out[(int(i_idx[t]), int(j_idx[t]))] for t in range(a.t_total)],
        axis=-3,
    )
    return CholeskyFactor(blocks, n, bn)
