"""Traced artifacts and the one canonical jaxpr traversal.

An :class:`Artifact` is what the rules see: a jaxpr (plus, optionally, the
compiled HLO text for collective accounting) together with the governing
:class:`repro.tune.cost.Plan` — the closed-form predictions the budget
rules compare the program against. ``overrides`` lets a call site without
a full plan (or with a stricter local contract than the plan implies) pin
individual rule parameters; see :mod:`repro.check.rules` for the keys each
rule reads.

:func:`walk_eqns` is the single recursive traversal that replaces the five
hand-rolled walkers the test suite used to carry: it descends into every
nested jaxpr reachable through equation params (``pjit``, ``shard_map``,
``scan``/``while``/``cond`` bodies, custom-call wrappers …) and — by
default — treats ``pallas_call`` bodies as opaque. In-kernel equations are
tile-granular by the kernels' block contract; the structural invariants the
rules police (dense squares, full transposes, dispatch counts) are
wrapper-level properties, so counting inside kernel bodies
would double-book every leaf. Pass ``into_pallas=True`` to audit kernel
bodies too.

:func:`trace_plan` is the harness entry: it traces the exact callable the
autotuner times (``tune.apply.build_callable``) on abstract operands of the
plan's shape, so the program the checker sees IS the program the plan
dispatches — no parallel re-implementation of the dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, Optional, Tuple

__all__ = ["Artifact", "EqnSite", "walk_eqns", "abstract_args",
           "plan_label", "trace_plan"]


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation plus its provenance in the traversal."""

    path: Tuple[str, ...]   # enclosing primitive names, outermost first
    index: int              # eqn index within its own jaxpr
    eqn: Any                # jax.core.JaxprEqn


@dataclasses.dataclass
class Artifact:
    """One traced program under one plan — the unit the rules analyze."""

    label: str
    jaxpr: Any                          # jax.core.Jaxpr (ClosedJaxpr.jaxpr)
    plan: Optional[Any] = None          # repro.tune.cost.Plan
    hlo_text: Optional[str] = None      # compiled per-device HLO, if lowered
    overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def sites(self, *, into_pallas: bool = False) -> Iterator[EqnSite]:
        return walk_eqns(self.jaxpr, into_pallas=into_pallas)


def _subjaxprs(value) -> Iterator[Any]:
    """Jaxprs reachable from one equation param value.

    Accepts a ClosedJaxpr (→ its ``.jaxpr``), a raw Jaxpr, or a list/tuple
    of either (``cond`` branches); anything else yields nothing.
    """
    for x in (value if isinstance(value, (list, tuple)) else (value,)):
        j = getattr(x, "jaxpr", x)      # ClosedJaxpr → Jaxpr; Jaxpr → itself
        if hasattr(j, "eqns") and hasattr(j, "outvars"):
            yield j


def walk_eqns(jaxpr, *, into_pallas: bool = False,
              _path: Tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Yield every equation of ``jaxpr`` and its nested jaxprs, depth-first.

    ``pallas_call`` bodies are opaque unless ``into_pallas=True`` (see
    module docstring). The yielded :class:`EqnSite` carries the enclosing
    primitive path and the eqn's index in its own jaxpr — the provenance
    findings report.
    """
    for i, eqn in enumerate(jaxpr.eqns):
        yield EqnSite(_path, i, eqn)
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        sub_path = _path + (eqn.primitive.name,)
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from walk_eqns(sub, into_pallas=into_pallas,
                                     _path=sub_path)


def abstract_args(plan) -> tuple:
    """Abstract operands matching ``tune.apply.build_callable(plan)``'s
    signature — the same shapes the autotuner would time."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(plan.dtype)
    lead = (plan.batch,) if plan.batch else ()
    a = jax.ShapeDtypeStruct(lead + (plan.m, plan.n), dt)
    if plan.op in ("gemm_tn", "solve"):
        b = jax.ShapeDtypeStruct(lead + (plan.m, plan.k), dt)
        return (a, b)
    return (a,)


def plan_label(plan) -> str:
    """Stable artifact label for a plan — the allowlist's match key."""
    parts = [
        plan.op, plan.algorithm, plan.leaf_dispatch,
        "kern" if plan.use_kernels else "xla", plan.out,
        f"{plan.m}x{plan.n}x{plan.k}", plan.dtype,
    ]
    if plan.method:
        parts.append(plan.method)
    if plan.devices > 1:
        parts.append(f"dist{plan.devices}")
    return ":".join(parts)


def trace_plan(plan, *, lower: bool = False,
               label: Optional[str] = None) -> Artifact:
    """Trace ``build_callable(plan)`` into an :class:`Artifact`.

    ``lower=True`` additionally compiles and attaches the per-device HLO
    text (one lowering, shared with the collective accounting — see
    :func:`repro.analysis.hlo.compiled_text`).
    """
    import jax

    from repro.tune import apply

    fn = apply.build_callable(plan)
    args = abstract_args(plan)
    closed = jax.make_jaxpr(fn)(*args)
    hlo = None
    if lower:
        from repro.analysis.hlo import compiled_text

        hlo = compiled_text(fn, *args)
    return Artifact(label=label or plan_label(plan), jaxpr=closed.jaxpr,
                    plan=plan, hlo_text=hlo)
