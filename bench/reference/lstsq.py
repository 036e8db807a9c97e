"""Plain references of the ridge least-squares solve, and the comparisons
that decide ``correct`` for the ``lstsq-*`` configurations.

It imports nothing of the program. ``solve`` forms the normal equations
``(AᵀA + λI)·x = Aᵀb`` with the products of :func:`bench.reference.gram.tn`
at the given precision, factors them with ``jnp.linalg.cholesky`` and
substitutes twice. :class:`Problem` holds a problem in float64 on the
host and reads the backward error of an answer to it, the number that
decides ``correct`` for every ``lstsq-*`` cell.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.gram import tn

__all__ = ["solve", "gram_norm", "Problem"]


def solve(a, b, ridge: float, precision: str = "highest"):
    """``x`` of shape ``b.shape[:-2] + (n, r)`` for ``b`` of ``(…, m, r)``."""
    n = a.shape[-1]
    g = tn(a, a, precision) + ridge * jnp.eye(n, dtype=jnp.float32)
    c = tn(a, b, precision)
    low = jnp.linalg.cholesky(g)
    y = jax.lax.linalg.triangular_solve(low, c, left_side=True, lower=True)
    return jax.lax.linalg.triangular_solve(low, y, left_side=True, lower=True,
                                           transpose_a=True)


def gram_norm(a, ridge: float) -> jax.Array:
    """``‖AᵀA + λI‖_F`` at full float32 precision, on the device."""
    n = a.shape[-1]
    return jnp.linalg.norm(tn(a, a, "highest") + ridge * jnp.eye(n, dtype=jnp.float32))


class Problem:
    """One least-squares problem in float64 on the host, for
    :meth:`backward_err`. ``g_norm`` (``‖AᵀA + λI‖_F``) may be given,
    computed elsewhere, where ``A`` is too large to square on the host."""

    def __init__(self, a, b, ridge: float, g_norm: float = None):
        self.a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        self.b = b[:, None] if b.ndim == 1 else b
        self.ridge = float(ridge)
        self.c_norm = float(np.linalg.norm(self.a.T @ self.b))
        if g_norm is None:
            g = self.a.T @ self.a + self.ridge * np.eye(self.a.shape[1])
            g_norm = float(np.linalg.norm(g))
        self.g_norm = g_norm

    def backward_err(self, x) -> float:
        """Normwise backward error of ``x`` for the normal equations
        ``G·x = c``, ``G = AᵀA + λI``, ``c = Aᵀb`` (Rigal–Gaches):
        ``‖Aᵀ(A·x − b) + λx‖ / (‖G‖·‖x‖ + ‖c‖)``, Frobenius norms, the
        residual in float64.

        It reads the rounding of the whole solve, Gram included, and not
        the problem's conditioning, which the forward error would mix in.
        A non-finite ``x`` reads ``inf``.
        """
        x64 = np.asarray(x, np.float64).reshape(self.a.shape[1], -1)
        r = self.a.T @ (self.a @ x64 - self.b) + self.ridge * x64
        scale = self.g_norm * np.linalg.norm(x64) + self.c_norm
        err = np.linalg.norm(r) / scale if scale > 0 else np.linalg.norm(r)
        return float(err) if np.isfinite(err) else float("inf")
