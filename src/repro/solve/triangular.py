"""Blocked forward/backward substitution against a packed Cholesky factor.

``solve_triangular`` runs the block recurrence on the packed factor grid —
multi-RHS, batched, and with no dense ``(n, n)`` anywhere:

    forward  (L·y = b):     y_i = L[i,i]⁻¹·(b_i − Σ_{j<i} L[i,j]·y_j)
    backward (Lᵀ·x = y):    x_i = L[i,i]⁻ᵀ·(y_i − Σ_{j>i} L[j,i]ᵀ·x_j)

The Σ terms are one batched NT/TN block einsum per step (tile-level ops —
``L[j,i]ᵀ`` transposes a ``bn×bn`` tile, never a matrix); the diagonal
solves go to the plan's base engine: the Pallas ``trsm`` kernel
(``X·Lᵀ = B`` / ``X·L = B`` on the transposed RHS tile) when
``plan.use_kernels``, else ``lax.linalg.triangular_solve``.

``solve_cholesky`` composes the two substitutions into a full
``A·x = b`` solve given ``A = L·Lᵀ``.

Right-hand sides: ``(..., n)`` or ``(..., n, r)`` with leading dims
matching the factor's batch dims (or none). Rows beyond ``n`` are
zero-padded onto the block grid; the factor's identity pad (see
``repro.solve.cholesky``) maps them back to zero, so the final crop is
exact.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import dot_precision
from repro.solve.cholesky import CholeskyFactor, _flat_call

__all__ = ["solve_triangular", "solve_cholesky"]


def _left_solve_jnp(l, c, *, transpose: bool):
    return jax.lax.linalg.triangular_solve(
        l, c, left_side=True, lower=True, transpose_a=transpose
    )


def _left_solve_kernel(l, c, *, transpose: bool):
    # L·y = c  ⇔  yᵀ·Lᵀ = cᵀ   (kernel transpose=True)
    # Lᵀ·y = c ⇔  yᵀ·L  = cᵀ   (kernel transpose=False)
    from repro.kernels import ops

    ct = jnp.swapaxes(c, -1, -2)
    yt = _flat_call(
        lambda lf, cf: ops.trsm(lf, cf, transpose=not transpose), l, ct
    )
    return jnp.swapaxes(yt, -1, -2)


def _diag_solver(plan, base_trsm: Optional[Callable]):
    if base_trsm is not None:
        return base_trsm
    if plan is not None and getattr(plan, "use_kernels", False):
        return _left_solve_kernel
    return _left_solve_jnp


def solve_triangular(
    f: CholeskyFactor,
    b: jax.Array,
    *,
    transpose: bool = False,
    plan=None,
    base_trsm: Optional[Callable] = None,
) -> jax.Array:
    """Solve ``L·y = b`` (``transpose=False``) or ``Lᵀ·x = b`` against the
    packed factor, blockwise. ``b``: ``(..., n)`` or ``(..., n, r)``;
    returns the matching shape. ``base_trsm(l, c, transpose=...)`` must
    solve the *left* diagonal-tile system on ``(..., bn, r)`` tiles.
    """
    nb, bn, n = f.nb, f.bn, f.n
    vector = b.ndim == f.blocks.ndim - 2  # (..., n) vs (..., n, r)
    if vector:
        b = b[..., None]
    if b.shape[-2] != n:
        raise ValueError(f"rhs rows {b.shape[-2]} != factor n {n}")
    pad = nb * bn - n
    if pad:
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)])
    batch = b.shape[:-2]
    r = b.shape[-1]
    bs = b.reshape(*batch, nb, bn, r)
    solve_diag = _diag_solver(plan, base_trsm)

    xs: dict = {}
    order = range(nb) if not transpose else range(nb - 1, -1, -1)
    for i in order:
        c = bs[..., i, :, :]
        if not transpose:
            done = range(i)  # subtract L[i,j]·y_j, j < i
            if done:
                lt = jnp.stack([f.block(i, j) for j in done], axis=0)
                xt = jnp.stack([xs[j] for j in done], axis=0)
                # f32 accumulation regardless of operand dtype (the
                # repro.check acc-dtype contract)
                c = c - jnp.einsum(
                    "k...ab,k...br->...ar", lt, xt,
                    precision=dot_precision(lt, xt),
                    preferred_element_type=jnp.float32,
                )
        else:
            done = range(i + 1, nb)  # subtract L[j,i]ᵀ·x_j, j > i
            if done:
                lt = jnp.stack([f.block(j, i) for j in done], axis=0)
                xt = jnp.stack([xs[j] for j in done], axis=0)
                c = c - jnp.einsum(
                    "k...ba,k...br->...ar", lt, xt,
                    precision=dot_precision(lt, xt),
                    preferred_element_type=jnp.float32,
                )
        xs[i] = solve_diag(f.block(i, i), c, transpose=transpose)

    x = jnp.concatenate([xs[i] for i in range(nb)], axis=-2)[..., :n, :]
    return x[..., 0] if vector else x


def solve_cholesky(
    f: CholeskyFactor,
    b: jax.Array,
    *,
    plan=None,
    base_trsm: Optional[Callable] = None,
) -> jax.Array:
    """Full SPD solve ``A·x = b`` given the packed factor ``A = L·Lᵀ``:
    forward then backward substitution, packed end-to-end."""
    y = solve_triangular(f, b, transpose=False, plan=plan, base_trsm=base_trsm)
    return solve_triangular(f, y, transpose=True, plan=plan, base_trsm=base_trsm)
