"""What the per-layer readers read of the program's own records
(``repro.obs``): device time under its named scopes, and the set-up steps
of the cell's program as JAX reported them.

Both read nothing, and return ``None``, from a program that lacks those
records: one whose ops carry none of the scopes asked for, or one without
``repro.obs.compiles``.
"""

from __future__ import annotations

from bench.trace import _scope

__all__ = ["scope_share", "setup_step"]


def scope_share(ctx, scopes):
    """Device time of the ops outside any Pallas launch whose innermost
    program scope (``bench.trace``'s rule: the last dotted part of the
    op's ``op_name``) is one of ``scopes``, over the device time of all ops
    in the traced window, in %."""
    red = ctx.reduced
    total = red.seconds(red.ops)
    ops = [o for o in red.ops
           if o.name not in ctx.launches and _scope(red.op_name(o)) in scopes]
    if not ops or total <= 0:
        return None
    return 100.0 * red.seconds(ops) / total


def setup_step(step):
    """Seconds of ``step`` (``trace``, ``lower`` or ``compile``) of the
    first program this process built that holds one of the program's root
    spans (``repro.obs.compiles.programs``, which needs obs enabled while
    it was traced, as ``--trace 1`` has it)."""
    try:
        from repro.obs import compiles
    except ImportError:
        return None
    progs = compiles.programs()
    return getattr(progs[0], f"{step}_s") if progs else None
