"""Entry ``gram_2x2``: the program's planned packed Gram of a row-sharded A.

The configuration's ``mesh`` (``{"data": 2, "model": 2}``) lays the first
four chips out as a ``("data", "model")`` mesh: A's rows are split over
``data``, the Gram's lower-triangle tiles over ``model``.
``tune.plan(op="ata", …, devices=2, row_devices=2)`` chooses the plan from
the analytic model, and ``tune.apply.ata_distributed_with_plan`` is the
jitted entry the window calls (``repro.core.distributed``: the planner's
BFS/DFS or psum schedule). Its answer is the packed ``SymmetricMatrix``,
its tiles sharded over the chips; what is compared is every tile
(``.blocks``) against the row-blocked plain reference
(``bench/reference/gram_2x2.py``).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference import gram_2x2 as ref

__all__ = ["plan", "program", "control", "operands", "answer", "check"]

ROW_AXIS, TASK_AXIS = "data", "model"


def _mesh(config) -> Mesh:
    d, p = config["mesh"][ROW_AXIS], config["mesh"][TASK_AXIS]
    devices = np.asarray(jax.devices()[:d * p]).reshape(d, p)
    return Mesh(devices, (ROW_AXIS, TASK_AXIS))


def plan(config, traffic):
    from repro import tune

    m, n = traffic["shape"]
    return tune.plan(op="ata", m=m, n=n, dtype=config["dtype"],
                     out=config["out"], devices=config["mesh"][TASK_AXIS],
                     row_devices=config["mesh"][ROW_AXIS])


def program(plan, config, traffic):
    from repro.tune.apply import ata_distributed_with_plan

    mesh = _mesh(config)
    return jax.jit(lambda a: ata_distributed_with_plan(
        a, mesh, plan, task_axis=TASK_AXIS, row_axis=ROW_AXIS))


def _tile(plan) -> int:
    from repro.core.symmetric import default_block_size

    return default_block_size(plan.n, plan.packed_block)


def _reference(bn, precision, config):
    mesh = _mesh(config)
    return jax.jit(lambda a: ref.packed_tiles(a, bn, precision, mesh, ROW_AXIS))


def control(plan, config, traffic):
    """The reference at three bf16 passes, in the program's place."""
    return _reference(_tile(plan), "high", config)


def operands(key, config, traffic):
    """A made on the chips, straight into its row sharding."""
    shape, dtype = tuple(traffic["shape"]), config["dtype"]
    rows = NamedSharding(_mesh(config), P(ROW_AXIS, None))
    return jax.jit(lambda k: (jax.random.normal(k, shape, dtype),),
                   out_shardings=(rows,))(key)


def answer(out):
    """The packed tiles ``(T, bn, bn)``."""
    return getattr(out, "blocks", out)


def check(ops, answers, plan, config, traffic) -> dict:
    """``tile_rel_err``: the worst tile of every kept answer of ``ops``."""
    want = _reference(answers[0].shape[-1], "highest", config)(*ops)
    err = jax.jit(ref.tile_rel_err)
    return {"tile_rel_err": max(float(err(got, want)) for got in answers)}
