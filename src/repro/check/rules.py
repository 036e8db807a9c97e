"""The rule registry: the repo's structural invariants as machine checks.

Every rule is a function ``(Artifact) -> list[Finding]`` registered under a
stable id. Rules are *self-gating*: each decides from the artifact's plan
(or ``overrides``) whether it applies, and returns ``[]`` when it doesn't —
so the runner can always throw the whole registry at every artifact.

The shipped rules, and the contracts they encode (DESIGN.md §9 carries the
full taxonomy; the source contracts live in ``kernels/__init__.py`` and the
module docstrings of ``core.ata`` / ``core.strassen`` / ``solve``):

================== ========================================================
``no-dense-square``   packed paths never materialize an ``(n, n)`` /
                      ``(n_pad, n_pad)`` square (paper Prop. 4.2's low(C)).
``dot-budget``        ``dot_general`` count equals the closed-form leaf
                      count the cost model prices (``tune.cost``).
``launch-budget``     ``pallas_call`` count equals the kernel-path closed
                      form and never exceeds ``cost.dispatch_calls``.
``no-full-transpose`` the TN contract: no 2-D transpose above tile
                      granularity, except the single dense-ATA root mirror.
``acc-dtype``         every dot accumulates at ≥ the plan's accumulator
                      width (f32) — sub-f32 accumulation never sneaks in
                      via dtype promotion.
``no-vmap-of-pallas`` kernels batch through their native leading grid
                      dimension, never through vmap.
``collective-budget`` reduction-collective bytes (all-reduce +
                      reduce-scatter, per device) stay within the
                      ``cost.retrieval_bytes`` payload the planner prices;
                      BFS-containing plans get the tighter one-chunk
                      reduce-scatter budget (``ceil(T/P)·w²``).
================== ========================================================

Override keys (``Artifact.overrides``) let plan-less call sites pin rule
parameters; each rule documents the keys it reads. Intentional violations
are suppressed through the report-level allowlist
(:class:`repro.check.findings.Allow`), never by weakening a rule.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

from repro.check.artifacts import Artifact
from repro.check.findings import Allow, Finding, Report

__all__ = ["Rule", "REGISTRY", "rule", "run", "run_many", "rule_ids"]


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    severity: str
    doc: str
    fn: Callable[[Artifact], List[Finding]]


REGISTRY: Dict[str, Rule] = {}


def rule(rule_id: str, severity: str = "error"):
    """Register a rule function under ``rule_id``."""

    def deco(fn):
        REGISTRY[rule_id] = Rule(rule_id, severity,
                                 (fn.__doc__ or "").strip(), fn)
        return fn

    return deco


def rule_ids() -> List[str]:
    return sorted(REGISTRY)


# ---------------------------------------------------------------------------
# shared plan geometry
# ---------------------------------------------------------------------------


def _finding(art: Artifact, rule_id: str, message: str, site=None,
             shape=None) -> Finding:
    return Finding(
        rule=rule_id, message=message, artifact=art.label,
        severity=REGISTRY[rule_id].severity if rule_id in REGISTRY else "error",
        primitive=site.eqn.primitive.name if site else None,
        path=site.path if site else (),
        eqn_index=site.index if site else None,
        shape=tuple(shape) if shape is not None else None,
    )


def _depth(plan) -> int:
    """Recursion depth of the plan's product tree (0 for algorithm='dense'
    and for trees the cutoff covers entirely)."""
    from repro.core.strassen import tree_depth

    if plan.algorithm == "dense":
        return 0
    dims = (plan.m, plan.n, plan.k) if plan.op == "gemm_tn" else (plan.m, plan.n)
    return tree_depth(dims, plan.n_base)


def _packed_bn(plan) -> int:
    """Effective packed block (the grid clamp every producer shares)."""
    from repro.core.symmetric import default_block_size

    return default_block_size(plan.n, plan.packed_block)


def _itemsize(dtype_str: str) -> int:
    import jax.numpy as jnp

    return jnp.dtype(dtype_str).itemsize


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


@rule("no-dense-square")
def no_dense_square(art: Artifact) -> List[Finding]:
    """Packed paths must never materialize a dense ``(n, n)`` or padded
    ``(n_pad, n_pad)`` square — the whole point of packed retrieval.

    Applies to plans with ``out='packed'`` (op='solve' included: the gram,
    factor, and substitutions are all packed-native). A degenerate
    single-block grid (``bn ≥ n``) legitimately holds the square as its one
    block, so the rule requires a real block grid. On the kernel path,
    ``pallas_call`` outputs padded up to the plan's block shapes can
    coincide with ``(n, n)`` when a block dim reaches ``n`` — padding
    granularity, not a gram square — so kernel launches whose output fits
    inside one block are exempt. Overrides: ``forbidden_squares`` —
    explicit set of (r, c) trailing shapes.
    """
    plan = art.plan
    forbidden = art.overrides.get("forbidden_squares")
    block_pad = 0
    if forbidden is None:
        if plan is None or plan.out != "packed":
            return []
        n = plan.n
        bn = _packed_bn(plan)
        if bn >= n:
            return []        # single-block grid: the square IS the block
        if plan.op != "solve" and _depth(plan) == 0:
            return []        # single-leaf gram: one (n, n) base tile is legal
        n_pad = -(-n // bn) * bn
        forbidden = {(n, n), (n_pad, n_pad)}
        if plan.use_kernels:
            block_pad = max(plan.syrk_blocks + plan.gemm_blocks)
    forbidden = {tuple(s) for s in forbidden}
    out = []
    for site in art.sites():
        if (site.eqn.primitive.name == "pallas_call" and block_pad
                and all(max(tuple(v.aval.shape)[-2:] or (0,)) <= block_pad
                        for v in site.eqn.outvars)):
            continue         # block-padded leaf tiles, bounded by the spec
        for v in site.eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if shape[-2:] in forbidden:
                out.append(_finding(
                    art, "no-dense-square",
                    f"dense square {shape} materialized on a packed path",
                    site, shape))
    return out


def _expected_dots(plan) -> Optional[int]:
    """Closed-form ``dot_general`` count of the XLA dispatch, or None when
    the rule has no exact form (see ``dot-budget`` docstring)."""
    from repro.tune import cost

    if plan.op in ("ata", "gemm_tn"):
        lv = _depth(plan)
        if lv == 0:
            return 1                       # one classical dot, any dispatch
        if plan.op == "ata":
            s, g = cost._ata_leaves(plan.m, plan.n, plan.n_base)
            return {"unrolled": s + g, "batched": 2}[plan.leaf_dispatch]
        leaves = cost._strassen_leaves(plan.m, plan.n, plan.k, plan.n_base)
        return {"unrolled": leaves, "batched": 1}[plan.leaf_dispatch]
    if plan.op == "solve":
        if plan.method == "cg":
            # Aᵀb + one loop body (A·p plus the planned TN leaves); the
            # body is traced once regardless of the iteration budget
            n_base = (max(plan.n_base, plan.m, plan.n)
                      if plan.algorithm == "dense" else plan.n_base)
            leaves = cost._strassen_leaves(plan.m, plan.n, plan.k, n_base)
            return 1 + 2 * leaves
        # factor: gram + Aᵀb + the blocked factor/substitution einsums
        # (per block column: one Schur update against the finished panel
        # row, one cross-panel update, and one update per substitution
        # pass — all lowering to dot_general)
        gram_plan = dataclasses.replace(plan, op="ata", k=plan.n)
        gram = _expected_dots(gram_plan)
        nbk = -(-plan.n // _packed_bn(plan))
        return gram + 1 + (nbk - 1) + max(nbk - 2, 0) + 2 * (nbk - 1)
    return None


@rule("dot-budget")
def dot_budget(art: Artifact) -> List[Finding]:
    """The jaxpr's ``dot_general`` count must equal the closed-form leaf
    count the cost model prices.

    This is the cost model cross-checked against the program it prices:
    unrolled = one dot per leaf (``cost._ata_leaves`` /
    ``cost._strassen_leaves`` — exactly ``cost.dispatch_calls``), batched =
    O(1) batched dots. Solve plans get
    the gram's form plus the factor/substitution einsum band (method=
    'factor') or the CG operator pair (method='cg'). Applies to XLA-path
    plans (``use_kernels=False``, unbatched); the kernel path is budgeted
    by ``launch-budget``. Override: ``expected_dots``.
    """
    plan = art.plan
    expected = art.overrides.get("expected_dots")
    if expected is None:
        if plan is None or plan.use_kernels or plan.batch:
            return []
        expected = _expected_dots(plan)
        if expected is None:
            return []
    got = sum(1 for s in art.sites()
              if s.eqn.primitive.name == "dot_general")
    if got == expected:
        return []
    return [_finding(
        art, "dot-budget",
        f"jaxpr dispatches {got} dot_general eqns; the closed form "
        f"predicts {expected}")]


@rule("launch-budget")
def launch_budget(art: Artifact) -> List[Finding]:
    """Kernel-path plans: the ``pallas_call`` count must equal the closed
    form (unrolled = one launch per leaf; batched = one per engine) and
    never exceed the
    ``cost.dispatch_calls`` budget the planner prices. Applies to product
    plans with ``use_kernels=True``. Override: ``expected_launches``.
    """
    from repro.tune import cost

    plan = art.plan
    expected = art.overrides.get("expected_launches")
    budget = art.overrides.get("launch_ceiling")
    if expected is None:
        if (plan is None or not plan.use_kernels or plan.batch
                or plan.op not in ("ata", "gemm_tn")):
            return []
        lv = _depth(plan)
        if lv == 0:
            expected = 1
        elif plan.op == "ata":
            s, g = cost._ata_leaves(plan.m, plan.n, plan.n_base)
            expected = {"unrolled": s + g, "batched": 2}[plan.leaf_dispatch]
        else:
            leaves = cost._strassen_leaves(plan.m, plan.n, plan.k,
                                           plan.n_base)
            expected = {"unrolled": leaves, "batched": 1}[plan.leaf_dispatch]
        budget = cost.dispatch_calls(
            plan.op, plan.algorithm, plan.m, plan.n, plan.k, plan.n_base,
            plan.leaf_dispatch)
    got = sum(1 for s in art.sites()
              if s.eqn.primitive.name == "pallas_call")
    out = []
    if got != expected:
        out.append(_finding(
            art, "launch-budget",
            f"jaxpr dispatches {got} pallas_call launches; the closed "
            f"form predicts {expected}"))
    if budget is not None and got > budget:
        out.append(_finding(
            art, "launch-budget",
            f"{got} pallas_call launches exceed the priced "
            f"dispatch_calls budget {budget}"))
    return out


@rule("no-full-transpose")
def no_full_transpose(art: Artifact) -> List[Finding]:
    """The TN contract: no 2-D transpose above tile granularity.

    ``Aᵀ`` is never materialized (paper §3) — the only transposes a planned
    program may contain are tile mirrors bounded by the recursion cutoff /
    packed block, plus, for dense-output ATA with a real recursion, exactly
    ONE root ``(n, n)`` mirror (the documented ``sym_tile`` finalize).
    Kernel bodies are opaque (their in-kernel tile mirrors are the base-case
    symmetry contract). Overrides: ``max_transpose_dim`` (tile bound;
    plans default to ``max(n_base, packed block)``), ``mirror_budget``.
    """
    plan = art.plan
    max_dim = art.overrides.get("max_transpose_dim")
    budget = art.overrides.get("mirror_budget")
    mirror_shape = None
    if plan is not None:
        if max_dim is None:
            max_dim = max(plan.n_base, _packed_bn(plan))
        if budget is None:
            # the root mirror exists at every depth: a single-leaf gram's
            # base syrk tril+mirror IS the (n, n) mirror
            budget = 1 if (plan.op == "ata" and plan.out == "dense") else 0
        mirror_shape = (plan.n, plan.n)
    if max_dim is None:
        return []
    budget = budget or 0
    mirror_shape = art.overrides.get("mirror_shape", mirror_shape)
    out, mirrors = [], 0
    for site in art.sites():
        if site.eqn.primitive.name != "transpose":
            continue
        shape = tuple(site.eqn.outvars[0].aval.shape)
        if len(shape) != 2 or max(shape) <= max_dim:
            continue
        if shape == mirror_shape and mirrors < budget:
            mirrors += 1
            continue
        out.append(_finding(
            art, "no-full-transpose",
            f"2-D transpose of {shape} exceeds the {max_dim}-tile bound "
            f"(materialized operand mirror)",
            site, shape))
    return out


@rule("acc-dtype")
def acc_dtype(art: Artifact) -> List[Finding]:
    """Every ``dot_general`` must accumulate at the plan accumulator width.

    jnp-level dots always carry a ``preferred_element_type`` (filled with
    the *promoted input dtype* when the caller doesn't pass one), so
    presence is meaningless — the rule checks the effective accumulation
    dtype: ``preferred_element_type`` if set, else the output dtype, must
    be at least as wide as the accumulator (f32, or the operand dtype when
    that is wider). A bf16 operand reaching a dot without an explicit
    ``preferred_element_type=f32`` shows up here as bf16 accumulation.
    Override: ``min_acc_itemsize``.
    """
    import jax.numpy as jnp

    plan = art.plan
    required = art.overrides.get("min_acc_itemsize")
    if required is None:
        required = max(4, _itemsize(plan.dtype)) if plan is not None else 4
    out = []
    for site in art.sites():
        if site.eqn.primitive.name != "dot_general":
            continue
        pref = site.eqn.params.get("preferred_element_type")
        eff = jnp.dtype(pref) if pref is not None else jnp.dtype(
            site.eqn.outvars[0].aval.dtype)
        if not jnp.issubdtype(eff, jnp.floating):
            continue
        if eff.itemsize < required:
            out.append(_finding(
                art, "acc-dtype",
                f"dot accumulates at {eff.name} "
                f"({eff.itemsize} B < required {required} B) — missing "
                f"preferred_element_type on the call site",
                site, tuple(site.eqn.outvars[0].aval.shape)))
    return out


@rule("no-vmap-of-pallas")
def no_vmap_of_pallas(art: Artifact) -> List[Finding]:
    """Kernel batching goes through the native leading grid dimension —
    one launch for the whole batch — never through ``vmap`` of a kernel
    (the batched-grid contract of ``kernels/__init__.py``). A vmapped
    ``pallas_call`` is visible in the jaxpr as a nonempty
    ``grid_mapping.vmapped_dims``. Applies to every artifact.
    """
    out = []
    for site in art.sites():
        if site.eqn.primitive.name != "pallas_call":
            continue
        gm = site.eqn.params.get("grid_mapping")
        dims = tuple(getattr(gm, "vmapped_dims", ()))
        if dims:
            out.append(_finding(
                art, "no-vmap-of-pallas",
                f"pallas_call batched via vmap (vmapped_dims={dims}); "
                f"use the kernel's native leading batch grid",
                site))
    return out


@rule("collective-budget")
def collective_budget(art: Artifact) -> List[Finding]:
    """Distributed plans: per-device reduction-collective bytes must stay
    within the retrieval payload the planner prices.

    The tile schedule psums the ``(T, w, w)`` stack and the rowshard path
    all-reduces the replicated result — in both cases the reduction-class
    payload (all-reduce + reduce-scatter) is bounded by
    ``cost.retrieval_bytes(out, nb, w)`` (measured exact for rowshard,
    ≲0.8× for the tile schedule; operand movement rides collective-permute
    / all-gather and is priced separately). A BFS-containing
    ``comm_schedule`` gets the far tighter scatter budget: the tri-direct
    reduce-scatter's whole reduction payload is ONE ``T_pad/P``-tile chunk
    per device (``ceil(T/P)·w²`` — the CAPS bandwidth saving the schedule
    exists for), so a BFS artifact whose reduction bytes regress to the
    psum schedule's full-stack payload fails the rule even though it would
    pass the psum budget. Needs compiled HLO text and a plan with a
    multi-device pool and a resolved ``nb``/``tile_w``. Overrides:
    ``collective_budget_bytes``, ``collective_slack`` (default 1.0).
    """
    from repro.analysis.hlo import collective_bytes
    from repro.tune import cost

    plan = art.plan
    if art.hlo_text is None:
        return []
    budget = art.overrides.get("collective_budget_bytes")
    if budget is None:
        if plan is None or plan.nb is None or plan.tile_w is None:
            return []
        pool = plan.devices * max(getattr(plan, "row_devices", 1), 1)
        if pool <= 1:
            return []
        cs = getattr(plan, "comm_schedule", None)
        if cs and "B" in cs:
            t_total = plan.nb * (plan.nb + 1) // 2
            budget = (
                -(-t_total // pool) * plan.tile_w * plan.tile_w
                * _itemsize(plan.dtype)
            )
        else:
            budget = cost.retrieval_bytes(
                plan.out, plan.nb, plan.tile_w, _itemsize(plan.dtype))
    slack = art.overrides.get("collective_slack", 1.0)
    by_kind = collective_bytes(art.hlo_text)
    reduction = by_kind["all-reduce"] + by_kind["reduce-scatter"]
    if reduction <= slack * budget:
        return []
    return [_finding(
        art, "collective-budget",
        f"reduction collectives move {reduction} B/device "
        f"(all-reduce {by_kind['all-reduce']}, reduce-scatter "
        f"{by_kind['reduce-scatter']}) > priced retrieval payload "
        f"{budget} B × slack {slack}")]


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------


def run(artifact: Artifact, rules: Optional[Sequence[str]] = None,
        allowlist: Sequence[Allow] = (),
        report: Optional[Report] = None) -> Report:
    """Run ``rules`` (default: the whole registry) over one artifact.

    Findings are partitioned by ``allowlist`` into the returned
    :class:`Report`; violation counters land in the ``repro.obs`` registry
    (``check.*`` — see DESIGN.md §8's naming table).
    """
    from repro.obs import metrics

    if report is None:
        report = Report(allowlist)
    ids = list(rules) if rules is not None else rule_ids()
    unknown = [i for i in ids if i not in REGISTRY]
    if unknown:
        raise KeyError(f"unknown rule ids {unknown}; have {rule_ids()}")
    n = 0
    for rid in ids:
        found = REGISTRY[rid].fn(artifact)
        kept = report.add(found)
        n += len(found)
        metrics.inc("check.rules_run")
        for f in kept:
            metrics.inc(f"check.findings.{f.rule}")
            if f.severity == "error":
                metrics.inc("check.violations")
    metrics.inc("check.artifacts")
    report.record_artifact(artifact.label, ids, n)
    return report


def run_many(artifacts: Sequence[Artifact],
             rules: Optional[Sequence[str]] = None,
             allowlist: Sequence[Allow] = ()) -> Report:
    report = Report(allowlist)
    for art in artifacts:
        run(art, rules=rules, allowlist=allowlist, report=report)
    return report
