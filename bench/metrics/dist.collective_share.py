"""``dist.collective_share``: device time in the chips' collectives, in %.

The collectives' device time (``bench.distributed_ops``: the schedule's
``distributed.psum`` / ``distributed.psum_scatter`` scopes and every
collective opcode), summed over every chip, over the device op time of
every chip in the traced window, control-flow ops left out. A chip that
waits for a slower one inside a collective counts the wait here. Nothing
to read where no collective ran.
"""

from bench.distributed_ops import is_collective, is_control_flow


def read(ctx):
    red = ctx.reduced
    ops = [o for o in red.ops if not is_control_flow(o)]
    total = red.seconds(ops)
    mine = [o for o in ops if is_collective(red, o)]
    if not mine or total <= 0:
        return None
    return 100.0 * red.seconds(mine) / total
