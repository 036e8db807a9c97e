"""``solve.lstsq`` — the front door of the packed solver layer.

One call closes the paper's loop end-to-end:

    x = solve.lstsq(A, b, ridge=…)

dispatched through ``repro.tune.plan(op="solve", m, n, k=r)``. The planner
prices the two methods with the exact counters of ``core.reference``
(potrf/trsm flops joined with the packed write-traffic model) and picks
per shape and RHS count:

* ``method='factor'`` — planned ``ata(out='packed')`` → packed blocked
  Cholesky → two packed triangular substitutions. **No dense ``(n, n)``
  exists anywhere in the jaxpr** (regression-tested): the gram arrives as
  the packed block pytree, the factor overwrites the same geometry, and
  the substitutions walk blocks.
* ``method='cg'`` — matrix-free CG on the gram operator (one planned TN
  product pair per iteration; the gram is never *formed* at all) for the
  regime where ``iters·4mnr`` undercuts ``mn² + n³/3``.

Pinning ``method=`` (or passing a frozen ``plan``) bypasses the planner,
with the same reproducibility contract as every other consumer of the
stack.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.solve.cg import cg_lstsq
from repro.solve.cholesky import cholesky
from repro.solve.triangular import solve_cholesky

__all__ = ["lstsq"]


def lstsq(
    a: jax.Array,
    b: jax.Array,
    *,
    ridge: float = 0.0,
    plan=None,
    method: Optional[str] = None,
    packed_block: Optional[int] = None,
    iters: Optional[int] = None,
    tol: Optional[float] = None,
) -> jax.Array:
    """Least squares ``min_x ‖A·x − b‖² + ridge·‖x‖²`` via the normal
    equations, packed-native.

    Args:
      a: ``(m, n)`` design matrix (any rectangular shape).
      b: ``(m,)`` or ``(m, r)`` right-hand side(s).
      ridge: Tikhonov term ``λ`` — added on the gram's logical diagonal
        (packed-native) before factoring, or inside the CG operator.
      plan: frozen :class:`repro.tune.Plan` with ``op='solve'`` carrying
        every tunable (method, gram algorithm/cutoff, packed block, base
        kernels). With no plan and no pinned ``method`` the dispatch is
        planned through ``repro.tune.plan`` — analytic model or cache.
      method: ``'factor'`` or ``'cg'`` — pinning it manually bypasses the
        planner (static defaults fill the rest, bitwise-reproducible).
      packed_block: packed grid block-size override (factor path).
      iters, tol: CG budget overrides (CG path).

    Returns:
      ``x``: ``(n,)`` or ``(n, r)``, matching ``b``.
    """
    if a.ndim != 2:
        raise ValueError(f"lstsq expects a 2-D design matrix, got {a.shape}")
    m, n = a.shape
    r = 1 if b.ndim == 1 else b.shape[-1]
    if b.shape[0] != m:
        raise ValueError(f"rhs rows {b.shape[0]} != design rows {m}")

    if plan is None and method is None:
        from repro import tune

        plan = tune.plan(
            op="solve", m=m, n=n, k=r, dtype=str(jnp.dtype(a.dtype)),
            out="packed",
        )
    if method is None:
        method = getattr(plan, "method", None) or "factor"
    if method not in ("factor", "cg"):
        raise ValueError(f"unknown solve method {method!r}; use 'factor' or 'cg'")
    # a pinned method with no plan bypasses the planner entirely — the
    # inner products run on the static defaults, so explicit calls stay
    # bitwise reproducible regardless of cache state (the same contract as
    # pinning n_base on ata; resolve_tunables' third regime).
    pinned = plan is None
    if pinned:
        from repro.tune import defaults as _defaults

        static_kw = dict(
            n_base=_defaults.DEFAULT_N_BASE, variant=_defaults.DEFAULT_VARIANT
        )

    obs.metrics.inc(f"dispatch.solve.{method}")
    if method == "cg":
        with obs.span("solve.lstsq", method="cg", m=m, n=n, r=r):
            if pinned:
                x = cg_lstsq(a, b, ridge=ridge, iters=iters, tol=tol,
                             **static_kw)
            else:
                x = cg_lstsq(a, b, ridge=ridge, iters=iters, tol=tol, plan=plan)
            return x

    # --- factor path: planned packed gram → packed Cholesky → substitutions
    from repro.core.ata import ata
    from repro.core.strassen import _dot_tn

    ata_plan = None
    ata_kw = {}
    if plan is not None:
        if packed_block is None:
            packed_block = plan.packed_block
        # predicted_s=None: the solve-level prediction prices the whole
        # pipeline, not the inner gram.
        ata_plan = dataclasses.replace(
            plan, op="ata", k=n, out="packed", method=None, predicted_s=None
        )
    else:
        ata_kw = static_kw
    with obs.span("solve.lstsq", method="factor", m=m, n=n, r=r):
        a32 = a.astype(jnp.float32)
        with obs.span("solve.gram"):
            gram = ata(a32, plan=ata_plan, out="packed",
                       packed_block=packed_block, **ata_kw)
        if ridge:
            gram = gram.add_scaled_identity(ridge)
        vector = b.ndim == 1
        b2 = (b[:, None] if vector else b).astype(jnp.float32)
        rhs = _dot_tn(a32, b2, jnp.float32)          # Aᵀb, Aᵀ never formed
        with obs.span("solve.cholesky"):
            factor = cholesky(gram, plan=plan)
        with obs.span("solve.substitution"):
            x = solve_cholesky(factor, rhs, plan=plan)
        x = x[..., 0] if vector else x
        return x
