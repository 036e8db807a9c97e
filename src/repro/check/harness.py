"""Tracing harnesses: the canonical plan grid and the distributed sweep.

:func:`canonical_plans` enumerates the (op × algorithm × leaf_dispatch ×
out × engine × dtype) grid the CI gate traces on every push — both
leaf dispatches, XLA and kernel (interpret) engines, dense and packed
outputs, both solver methods, plus a bf16 row per product op so the
``acc-dtype`` rule has sub-f32 operands to police. Shapes are rectangular
(``m ≠ n ≠ k``) on purpose: several rules' shape discriminators (operand
stacks vs product stacks, dense squares vs row slabs) need the dims
distinguishable, and ``n_base=32`` forces a depth-2 ATA tree / depth-1
Strassen tree so every budget has a real recursion to count.

:func:`distributed_plans` / :func:`run_distributed` are the multi-device
half — the tile-parallel and rowshard schedules traced through
``shard_map`` on the active mesh, plus the BFS/DFS schedule
(:func:`bfsdfs_plans` — planner-selected interleaving on a 2-axis
(row, task) submesh) — compiled once (the ``analysis.hlo.compiled_text``
path shared with the collective accounting), and checked against the
packed structural rules plus ``collective-budget`` (which holds
BFS artifacts to the tighter one-chunk reduce-scatter budget). CI runs
it inside the distributed-smoke job's 8-fake-CPU-device subprocess.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

from repro.check.artifacts import Artifact, plan_label, trace_plan
from repro.check.findings import Allow, Report
from repro.check import rules as _rules

__all__ = [
    "CANONICAL_SHAPE", "DEFAULT_ALLOWLIST",
    "canonical_plans", "run_grid", "distributed_plans", "bfsdfs_plans",
    "run_distributed", "run_serve",
]

# (m, n, k): rectangular; n_base forces L=2 on the ATA tree, L=1 on the
# gemm tree; packed_block gives a real 4-stripe packed grid at n=128.
CANONICAL_SHAPE = dict(m=192, n=128, k=64, n_base=32, packed_block=32)

# Intentional violations, suppressed by policy rather than by weakening a
# rule (DESIGN.md §9). Currently empty: every canonical artifact is clean.
DEFAULT_ALLOWLIST: List[Allow] = []


def canonical_plans() -> List:
    """The canonical plan grid (see module docstring)."""
    from repro.tune import cost

    m, n, k = (CANONICAL_SHAPE[d] for d in ("m", "n", "k"))
    nb, pb = CANONICAL_SHAPE["n_base"], CANONICAL_SHAPE["packed_block"]

    def mk(base, **kw):
        kw.setdefault("n_base", nb)
        kw.setdefault("packed_block", pb)
        return dataclasses.replace(base, **kw)

    ata = cost.default_plan("ata", m, n)
    gemm = cost.default_plan("gemm_tn", m, n, k)
    solve = cost.default_plan("solve", m, n, k, out="packed")

    plans = []
    # the product grid: both leaf dispatches × both engines × both outs
    for uk in (False, True):
        for ld in ("unrolled", "batched"):
            for out in ("dense", "packed"):
                plans.append(mk(ata, algorithm="strassen", leaf_dispatch=ld,
                                use_kernels=uk, out=out))
            plans.append(mk(gemm, algorithm="strassen", leaf_dispatch=ld,
                            use_kernels=uk))
    # algorithm row: the single classical dot and the winograd variant
    plans.append(mk(ata, algorithm="dense", leaf_dispatch="unrolled",
                    use_kernels=False))
    plans.append(mk(gemm, algorithm="dense", leaf_dispatch="unrolled",
                    use_kernels=False))
    plans.append(mk(ata, algorithm="winograd", leaf_dispatch="unrolled",
                    use_kernels=False, out="packed"))
    # bf16 row: sub-f32 operands — the acc-dtype rule's real quarry
    plans.append(mk(ata, algorithm="strassen", leaf_dispatch="unrolled",
                    use_kernels=False, dtype="bfloat16"))
    plans.append(mk(gemm, algorithm="strassen", leaf_dispatch="unrolled",
                    use_kernels=False, dtype="bfloat16"))
    # the solve path: both methods, packed-native
    plans.append(mk(solve, algorithm="strassen", method="factor"))
    plans.append(mk(solve, algorithm="strassen", method="cg"))
    return plans


def _quick_plans() -> List:
    """A three-artifact subset for smoke tests (one per op)."""
    plans = canonical_plans()
    picks = {}
    for p in plans:
        key = p.op
        if key not in picks and not p.use_kernels:
            picks[key] = p
    return list(picks.values())


def run_grid(plans: Optional[Sequence] = None, *,
             rules: Optional[Sequence[str]] = None,
             allowlist: Optional[Sequence[Allow]] = None,
             lower: bool = False, quick: bool = False,
             verbose: bool = False) -> Report:
    """Trace every plan and run the registry over each artifact."""
    if plans is None:
        plans = _quick_plans() if quick else canonical_plans()
    report = Report(DEFAULT_ALLOWLIST if allowlist is None else allowlist)
    for plan in plans:
        if verbose:
            print(f"  tracing {plan_label(plan)}", flush=True)
        art = trace_plan(plan, lower=lower)
        _rules.run(art, rules=rules, allowlist=report.allowlist,
                   report=report)
    return report


# ---------------------------------------------------------------------------
# distributed sweep (requires a multi-device backend, e.g. the CI job's
# XLA_FLAGS=--xla_force_host_platform_device_count=8 subprocess)
# ---------------------------------------------------------------------------

# m sized so the per-device rowshard slab (m/8 rows) still recurses past
# the cutoff — a sub-cutoff slab is a legitimate single-leaf gram whose
# (n, n) base tile the no-dense-square rule rightly exempts, and a harness
# should exercise the non-degenerate contract.
_DIST_SHAPE = dict(m=1024, n=512, n_base=64)
_DIST_RULES = ("no-dense-square", "no-vmap-of-pallas", "acc-dtype",
               "collective-budget")


def distributed_plans(devices: int) -> List:
    """Tile-parallel and rowshard plans (dense + packed) for ``devices``."""
    from repro.tune import cost

    m, n, nb_cut = _DIST_SHAPE["m"], _DIST_SHAPE["n"], _DIST_SHAPE["n_base"]
    plans = []
    for out in ("dense", "packed"):
        # default_plan's distributed branch resolves (nb, tile_w) through
        # the same tiling search ata_tile_parallel uses internally
        plans.append(dataclasses.replace(
            cost.default_plan("ata", m, n, out=out, devices=devices),
            algorithm="strassen", n_base=nb_cut))
    return plans


def bfsdfs_plans(devices: int, row_devices: int) -> List:
    """BFS/DFS plans (dense + packed) for a (row, task) 2-axis mesh.

    The interleaving is *planner-selected* — the top BFS-containing
    candidate of ``cost.candidates`` for the harness shape and mesh — so
    the artifact compiles exactly the schedule the front door would
    dispatch, and the collective-budget rule gates its one-chunk
    reduce-scatter payload.
    """
    from repro.tune import cost

    m, n, nb_cut = _DIST_SHAPE["m"], _DIST_SHAPE["n"], _DIST_SHAPE["n_base"]
    plans = []
    for out in ("dense", "packed"):
        cands = cost.candidates("ata", m, n, out=out, devices=devices,
                                row_devices=row_devices)
        top_b = next(
            (p for p in cands if p.comm_schedule and "B" in p.comm_schedule),
            None)
        if top_b is not None:
            plans.append(dataclasses.replace(top_b, n_base=nb_cut))
    return plans


def _trace_distributed(plan, mesh, schedule: str, *, m_global=None) -> Artifact:
    """Trace + compile one distributed schedule into an Artifact.

    ``plan`` is the plan the *rules* see (for rowshard: per-device row
    count); ``m_global`` is the traced input's row count when it differs.
    """
    import jax

    from repro.analysis.hlo import compiled_text

    a_abs = jax.ShapeDtypeStruct((m_global or plan.m, plan.n), "float32")
    if schedule == "tile":
        from repro.core.distributed import ata_tile_parallel

        fn = jax.jit(functools.partial(
            ata_tile_parallel, mesh=mesh, task_axis="model",
            n_base=plan.n_base, nb=plan.nb, out=plan.out))
    elif schedule == "bfsdfs":
        from repro.core.distributed import ata_bfs_dfs

        fn = jax.jit(functools.partial(
            ata_bfs_dfs, mesh=mesh, task_axis="model",
            row_axis=("data" if "data" in mesh.shape else None),
            interleaving=plan.comm_schedule, n_base=plan.n_base,
            nb=plan.nb, packed_block=plan.packed_block, out=plan.out))
    else:
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        from repro.core.distributed import gram_rowshard

        run = functools.partial(
            gram_rowshard, axis="model", n_base=plan.n_base, out=plan.out,
            packed_block=plan.packed_block)
        fn = jax.jit(shard_map(run, mesh=mesh, in_specs=P("model", None),
                               out_specs=P()))
    closed = jax.make_jaxpr(fn)(a_abs)
    hlo = compiled_text(fn, a_abs)
    return Artifact(label=f"{schedule}:{plan_label(plan)}",
                    jaxpr=closed.jaxpr, plan=plan, hlo_text=hlo)


def _serve_expected_dots(spec, sp) -> int:
    """Closed-form ``dot_general`` count of one serve bucket callable.

    The batched pipeline's dot count is batch-invariant (the batch dim
    rides every dot; the per-slice substitution solves are
    ``triangular_solve``, not dots), so lstsq buckets reuse the solve
    closed form of :func:`rules._expected_dots` verbatim. Whiten buckets
    drop the ``Aᵀb`` dot and the backward-substitution einsum band:
    gram + factor Schur band + ONE substitution pass.
    """
    if spec.op == "lstsq":
        return _rules._expected_dots(sp)
    gram_plan = dataclasses.replace(sp, op="ata", k=sp.n)
    gram = _rules._expected_dots(gram_plan)
    nbk = -(-sp.n // _rules._packed_bn(sp))
    return gram + (nbk - 1) + max(nbk - 2, 0) + (nbk - 1)


# the serve-path rule set: the packed/structural contracts the bucket
# callables must honor (dot-budget rides the explicit override above;
# launch-budget self-gates on the XLA-path smoke buckets but stays in the
# list so kernel-path bucket configs are covered the day they exist)
_SERVE_RULES = ("no-dense-square", "acc-dtype", "no-vmap-of-pallas",
                "dot-budget", "launch-budget")


def run_serve(*, config=None, steady_batches: int = 2,
              verbose: bool = False) -> Report:
    """Check the serve layer: trace every bucket callable of the (smoke)
    lattice against the packed/structural rules, then run a warmed
    steady-state loop and assert it performs **zero retraces**.

    The traced program IS the program a flush dispatches
    (``Server.bucket_callable`` — no parallel re-implementation), traced
    on the bucket's static abstract operands. The artifact carries the
    *batched* plan (the program's real identity) plus the
    ``expected_dots`` override computed from the unbatched solve closed
    form (see :func:`_serve_expected_dots`).

    The retrace half is dynamic by nature: a warmed :class:`Server`
    serves ``steady_batches`` full flushes per bucket; any growth of a
    jit cache past the warm floor lands as a ``serve-no-retrace``
    finding (plus the engine's own ``serve.retraces`` counter).
    """
    import numpy as np

    from repro.check.findings import Finding
    from repro.serve.engine import Server, serve_abstract_args, smoke_config
    from repro.serve.queue import Request

    if config is None:
        config = smoke_config()
    server = Server(config)
    report = Report(DEFAULT_ALLOWLIST)

    import jax

    for spec in config.buckets:
        if verbose:
            print(f"  tracing serve:{spec.label()}", flush=True)
        fn, sp = server.bucket_callable(spec)
        closed = jax.make_jaxpr(fn)(*serve_abstract_args(spec))
        batched = dataclasses.replace(sp, batch=spec.batch)
        art = Artifact(
            label=f"serve:{spec.label()}", jaxpr=closed.jaxpr, plan=batched,
            overrides={"expected_dots": _serve_expected_dots(spec, sp)})
        _rules.run(art, rules=_SERVE_RULES, allowlist=report.allowlist,
                   report=report)

    # steady-state: warm, then flush full batches and hold the jit caches
    # to the warm floor (the engine raises on strict_retrace — the harness
    # wants a Finding instead, so it serves in counter mode)
    if verbose:
        print("  warming serve steady-state loop", flush=True)
    server = Server(dataclasses.replace(config, strict_retrace=False))
    server.warm()
    rng = np.random.default_rng(0)
    for spec in config.buckets:
        for _ in range(steady_batches):
            for _i in range(spec.batch):
                a = rng.standard_normal((spec.m, spec.n)).astype(spec.dtype)
                rows = spec.m if spec.op == "lstsq" else spec.n
                b = rng.standard_normal((rows, spec.r)).astype(spec.dtype)
                server.submit(Request(op=spec.op, a=a, b=b))
    server.drain()
    findings = []
    if server.retraces():
        findings.append(Finding(
            rule="serve-no-retrace",
            message=f"steady-state loop retraced {server.retraces()} times "
                    "after the warm pass (compile-cache floor exceeded)",
            artifact="serve:steady-state"))
    report.add(findings)
    report.record_artifact("serve:steady-state", ["serve-no-retrace"],
                           len(findings))
    return report


def run_distributed(*, mesh=None,
                    allowlist: Optional[Sequence[Allow]] = None,
                    verbose: bool = False) -> Report:
    """Check the SPMD schedules on the active (or given) mesh."""
    import jax

    if mesh is None:
        p = jax.device_count()
        if p < 2:
            raise RuntimeError(
                "run_distributed needs >1 device; run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        mesh = jax.make_mesh((p,), ("model",))
    p = mesh.shape["model"]
    report = Report(DEFAULT_ALLOWLIST if allowlist is None else allowlist)
    for plan in distributed_plans(p):
        for schedule in ("tile", "rowshard"):
            if schedule == "rowshard":
                if plan.m % p:
                    continue
                # rowshard has no stripe tiling of its own: its reduction
                # payload is the replicated result — the packed block grid.
                # The artifact is the *per-device* program, so the plan
                # carries the local row count (depth gates resolve against
                # the slab each device actually recurses on).
                from repro.core.symmetric import default_block_size

                bn = default_block_size(plan.n, plan.packed_block)
                plan_r = dataclasses.replace(
                    plan, m=plan.m // p, nb=-(-plan.n // bn), tile_w=bn)
            else:
                plan_r = plan
            if verbose:
                print(f"  tracing {schedule}:{plan_label(plan_r)}",
                      flush=True)
            art = _trace_distributed(plan_r, mesh, schedule,
                                     m_global=plan.m)
            _rules.run(art, rules=_DIST_RULES, allowlist=report.allowlist,
                       report=report)
    # BFS/DFS artifacts on a 2-axis (row, task) submesh of the same
    # devices: the planner-selected interleaving, gated by the tighter
    # one-chunk scatter budget of the collective-budget rule. The row
    # axis is 4 so the per-device slab (m/4 = 256 rows) stays
    # distinguishable from the (n, n) square the no-dense-square rule
    # hunts (a 2-way split of the 1024×512 harness shape would make the
    # operand slab square).
    if p >= 4 and p % 4 == 0:
        import numpy as np
        from jax.sharding import Mesh

        mesh2 = Mesh(
            np.asarray(mesh.devices).reshape(4, p // 4), ("data", "model"))
        for plan in bfsdfs_plans(p // 4, 4):
            if verbose:
                print(f"  tracing bfsdfs:{plan_label(plan)}", flush=True)
            art = _trace_distributed(plan, mesh2, "bfsdfs",
                                     m_global=_DIST_SHAPE["m"])
            _rules.run(art, rules=_DIST_RULES, allowlist=report.allowlist,
                       report=report)
    return report
