"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package is validated against these references across a
shape × dtype sweep (see ``tests/test_kernels.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import dot_precision

__all__ = ["syrk_ref", "gemm_tn_ref"]


def gemm_tn_ref(a: jax.Array, b: jax.Array, alpha: float = 1.0) -> jax.Array:
    """``C = alpha·AᵀB`` with f32 accumulation, f32 output."""
    out = jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), precision=dot_precision(a, b),
        preferred_element_type=jnp.float32,
    )
    return (alpha * out).astype(jnp.float32)


def syrk_ref(a: jax.Array, alpha: float = 1.0) -> jax.Array:
    """``C = alpha·AᵀA`` full symmetric, f32 accumulation/output.

    Mirrors the kernel's exact-symmetry contract: the lower triangle is
    computed and reflected, so ``C == Cᵀ`` bitwise.
    """
    c = gemm_tn_ref(a, a, alpha)
    low = jnp.tril(c)
    return low + jnp.tril(c, -1).T
