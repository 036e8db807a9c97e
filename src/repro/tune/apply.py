"""Thread a frozen Plan into the ATA stack's executables.

The consumers (`core.ata`, `core.strassen`, `core.distributed`,
`kernels.ops`) accept ``plan=`` and resolve their tunables from it; this
module holds the pieces that need to look *down* the stack — building base
kernels from a plan's block shapes and building the jitted callable the
autotuner times — so `core` never imports `kernels` and `tune.search`
never special-cases ops.
"""

from __future__ import annotations

import functools

import jax

from repro.tune import cost

__all__ = [
    "base_fns",
    "build_callable",
    "ata_with_plan",
    "ata_distributed_with_plan",
    "gemm_tn_with_plan",
    "lstsq_with_plan",
]


def base_fns(plan: cost.Plan):
    """(base_syrk, base_dot) for the recursion bottom under this plan.

    ``use_kernels=True`` → the Pallas kernels with the plan's block shapes
    (compiled on TPU, interpret elsewhere — `kernels.ops` decides);
    otherwise ``(None, None)`` so the recursion keeps its MXU-native
    ``dot_general`` base case.
    """
    if not plan.use_kernels:
        return None, None
    from repro.kernels import ops

    base_syrk = functools.partial(ops.syrk, blocks=plan.syrk_blocks)
    base_dot = functools.partial(ops.gemm_tn, blocks=plan.gemm_blocks)
    return base_syrk, base_dot


def ata_with_plan(a, plan: cost.Plan, **kw):
    """``ata``/``ata_batched`` dispatched exactly as the plan says."""
    from repro.core.ata import ata, ata_batched

    fn = ata_batched if plan.batch else ata
    return fn(a, plan=plan, out=plan.out, **kw)


def ata_distributed_with_plan(
    a, mesh, plan: cost.Plan, *, task_axis: str = "model",
    row_axis=None, **kw,
):
    """Distributed ATA dispatched exactly as the plan says.

    The ``comm_schedule`` axis picks the SPMD schedule itself: a
    BFS-containing interleaving runs :func:`~repro.core.distributed.
    ata_bfs_dfs` (tri-direct reduce-scatter over the merged device pool);
    ``None`` or a pure-``'D'`` string runs the owner-computes psum
    schedule (:func:`~repro.core.distributed.ata_tile_parallel` — which a
    pure-``'D'`` ``ata_bfs_dfs`` degenerates to bitwise anyway, so the
    front door dispatches both to the same compiled program family).
    """
    from repro.core.distributed import ata_bfs_dfs, ata_tile_parallel

    cs = getattr(plan, "comm_schedule", None)
    if cs and "B" in cs:
        return ata_bfs_dfs(
            a, mesh, task_axis=task_axis, row_axis=row_axis, plan=plan,
            interleaving=cs, out=plan.out, **kw,
        )
    return ata_tile_parallel(
        a, mesh, task_axis=task_axis, row_axis=row_axis, plan=plan,
        out=plan.out, **kw,
    )


def gemm_tn_with_plan(a, b, plan: cost.Plan, **kw):
    from repro.core.strassen import strassen_tn

    return strassen_tn(a, b, plan=plan, **kw)


def lstsq_with_plan(a, b, plan: cost.Plan, **kw):
    """``solve.lstsq`` dispatched exactly as the plan says (method, gram
    tunables, base kernels)."""
    from repro.solve.lstsq import lstsq

    return lstsq(a, b, plan=plan, **kw)


def build_callable(plan: cost.Plan):
    """One jitted function executing the plan (what the autotuner times)."""
    if plan.op == "gemm_tn":
        return jax.jit(lambda a, b: gemm_tn_with_plan(a, b, plan))
    if plan.op == "solve":
        return jax.jit(lambda a, b: lstsq_with_plan(a, b, plan))
    return jax.jit(lambda a: ata_with_plan(a, plan))
