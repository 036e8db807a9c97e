"""Entry ``gram``: the program's planned packed Gram.

``tune.plan(op="ata", out="packed", …)`` chooses the plan from the analytic
model, and ``tune.apply.build_callable(plan)`` is the jitted entry the
window calls: ``ata`` for a 2-D operand, ``ata_batched`` for a 3-D stack
of blocks. Its answer is the packed ``SymmetricMatrix``; what is compared
is every one of its tiles (``.blocks``) against the plain reference.
"""

from __future__ import annotations

import jax

from bench.reference import gram as ref

__all__ = ["plan", "program", "control", "operands", "answer", "check"]


def _dims(traffic):
    shape = traffic["shape"]
    if len(shape) == 3:
        return shape[0], shape[1], shape[2]
    return 0, shape[0], shape[1]


def plan(config, traffic):
    from repro import tune

    batch, m, n = _dims(traffic)
    return tune.plan(op="ata", m=m, n=n, batch=batch, dtype=config["dtype"],
                     out=config["out"])


def program(plan, config, traffic):
    from repro.tune.apply import build_callable

    return build_callable(plan)


def _tile(plan) -> int:
    from repro.core.symmetric import default_block_size

    return default_block_size(plan.n, plan.packed_block)


def control(plan, config, traffic):
    """The reference at three bf16 passes, in the program's place."""
    bn = _tile(plan)
    return jax.jit(lambda a: ref.packed_tiles(a, bn, "high"))


def operands(key, config, traffic):
    shape, dtype = tuple(traffic["shape"]), config["dtype"]
    return jax.jit(lambda k: (jax.random.normal(k, shape, dtype),))(key)


def answer(out):
    """The packed tiles ``(…, T, bn, bn)``."""
    return getattr(out, "blocks", out)


def check(ops, answers, plan, config, traffic) -> dict:
    """``tile_rel_err``: the worst tile of every kept answer of ``ops``."""
    bn = answers[0].shape[-1]
    want = jax.jit(lambda a: ref.packed_tiles(a, bn, "highest"))(*ops)
    err = jax.jit(ref.tile_rel_err)
    return {"tile_rel_err": max(float(err(got, want)) for got in answers)}
