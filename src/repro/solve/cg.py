"""Matrix-free conjugate gradient on the gram *operator*.

For the tall-skinny / ill-conditioned-budget regime the planner can decide
that factoring the gram is not worth it: ``cg_lstsq`` solves the ridge
normal equations

    (AᵀA + λI)·x = Aᵀb

without ever *forming* ``AᵀA`` — each CG iteration applies the operator as
one planned TN product pair,

    p ↦ Aᵀ(A·p) + λp        (``A·p`` a plain dot, ``Aᵀ(·)`` the planned
                             FastStrassen TN product — ``Aᵀ`` is never
                             materialized, per the paper's Section 3),

so the resident footprint is ``O(m·r + n·r)`` instead of the ``O(n²)``
gram. Multi-RHS: the textbook iteration runs vectorized over the ``r``
columns with per-column step sizes; converged columns freeze (their
updates are masked to zero), so one fixed-trip ``fori_loop`` serves every
column — jit-stable, no host sync.

``cg_gram`` is the generic SPD-operator CG the lstsq wrapper builds on.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.precision import dot_precision

__all__ = ["cg_gram", "cg_lstsq"]


def cg_gram(
    matvec: Callable,
    b: jax.Array,
    *,
    iters: int,
    tol: float = 1e-6,
    x0: Optional[jax.Array] = None,
) -> jax.Array:
    """CG for ``G·x = b`` with SPD operator ``matvec: (n, r) → (n, r)``.

    ``b``: ``(n,)`` or ``(n, r)``; columns iterate independently (separate
    α/β per column) inside one vectorized loop. Stops *updating* a column
    once its residual norm falls below ``tol·‖b‖`` — the loop itself is a
    fixed-trip ``fori_loop`` so the schedule is static under jit.
    """
    vector = b.ndim == 1
    if vector:
        b = b[:, None]
    b = b.astype(jnp.float32)
    x = jnp.zeros_like(b) if x0 is None else x0.astype(jnp.float32)
    r0 = b - matvec(x) if x0 is not None else b
    stop2 = (tol * tol) * jnp.maximum(jnp.sum(b * b, axis=0), 1e-30)

    def body(_, carry):
        x, r, p, rs = carry
        live = rs > stop2                           # per-column progress mask
        gp = matvec(p)
        denom = jnp.sum(p * gp, axis=0)
        alpha = jnp.where(live, rs / jnp.maximum(denom, 1e-30), 0.0)
        x = x + alpha * p
        r = r - alpha * gp
        rs_new = jnp.sum(r * r, axis=0)
        beta = jnp.where(live, rs_new / jnp.maximum(rs, 1e-30), 0.0)
        p = r + beta * p
        return x, r, p, rs_new

    rs = jnp.sum(r0 * r0, axis=0)
    x, _, _, _ = jax.lax.fori_loop(0, iters, body, (x, r0, r0, rs))
    return x[:, 0] if vector else x


def cg_lstsq(
    a: jax.Array,
    b: jax.Array,
    *,
    ridge: float = 0.0,
    iters: Optional[int] = None,
    tol: Optional[float] = None,
    plan=None,
    gemm_plan=None,
    n_base: Optional[int] = None,
    variant: Optional[str] = None,
) -> jax.Array:
    """Ridge least squares via CG on the normal-equations operator.

    ``a``: ``(m, n)``; ``b``: ``(m,)`` or ``(m, r)``. Each iteration is one
    planned TN product pair — the dispatch of the ``Aᵀ(·)`` product comes,
    in order, from ``gemm_plan``, explicit ``n_base``/``variant`` pins
    (bitwise-reproducible static dispatch — what ``lstsq(method='cg')``
    passes), the solve ``plan``'s algorithm tunables, or the front door.
    Iteration budget and tolerance default to ``repro.tune.defaults``
    (``CG_MAX_ITERS`` capped by ``n`` — exact termination in exact
    arithmetic — and ``CG_TOL``).
    """
    from repro.core.strassen import strassen_tn
    from repro.tune import defaults

    if a.ndim != 2:
        raise ValueError(f"cg_lstsq expects a 2-D operand, got {a.shape}")
    m, n = a.shape
    if iters is None:
        iters = min(n, defaults.CG_MAX_ITERS)
    if tol is None:
        tol = defaults.CG_TOL
    a = a.astype(jnp.float32)
    vector = b.ndim == 1
    b2 = (b[:, None] if vector else b).astype(jnp.float32)

    kw = {}
    if gemm_plan is not None:
        kw["plan"] = gemm_plan
    elif n_base is not None or variant is not None:
        kw["n_base"] = n_base
        kw["variant"] = variant
    elif plan is not None and getattr(plan, "algorithm", None) is not None:
        # inherit the solve plan's algorithm tunables for the TN products
        # ('dense' expresses itself as a cutoff covering the whole operand,
        # same as resolve_tunables does for product plans)
        kw["n_base"] = (
            max(plan.n_base, m, n) if plan.algorithm == "dense" else plan.n_base
        )
        kw["variant"] = plan.variant

    obs.metrics.inc("solve.cg.calls")
    # the fixed trip count IS the iteration budget (columns converge by
    # freezing inside the loop, not by exiting it)
    obs.metrics.set_gauge("solve.cg.iters", iters)

    def matvec(p):
        # (m, r) plain NN dot — accumulation width pinned so the operator
        # keeps f32 accumulation even if the cast above is ever relaxed to
        # sub-f32 operands (the repro.check acc-dtype contract)
        ap = jnp.matmul(a, p, precision=dot_precision(a, p),
                        preferred_element_type=jnp.float32)
        atap = strassen_tn(a, ap, **kw)    # Aᵀ(A·p): planned TN product
        return atap + ridge * p if ridge else atap

    with obs.span("solve.cg", iters=iters, m=m, n=n):
        rhs = strassen_tn(a, b2, **kw)     # Aᵀb — same planned TN dispatch
        x = cg_gram(matvec, rhs, iters=iters, tol=tol)
    return x[:, 0] if vector else x
