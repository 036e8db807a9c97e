"""Matmul precision: float32 operands multiply at float32 precision.

A TPU's default precision multiplies float32 operands in one bf16 pass, in
XLA and in Pallas kernels alike: on a v5e a float32 Gram then carries a
relative error of about 2e-3 (PERF.md, Findings). Every matmul on the
Gram → solve → serve path asks for ``HIGHEST`` when an operand is float32
(or wider) and leaves bf16 operands, whose products the MXU forms exactly,
at the default. The CPU computes float32 products exactly either way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["dot_precision"]


def dot_precision(*operands):
    """``Precision.HIGHEST`` if an operand is a float of 32 bits or more,
    else ``None`` (the default)."""
    for x in operands:
        dt = jnp.dtype(x.dtype)
        if jnp.issubdtype(dt, jnp.floating) and dt.itemsize >= 4:
            return jax.lax.Precision.HIGHEST
    return None
