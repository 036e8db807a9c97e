"""``dist.imbalance``: how far the busiest chip's work exceeds the mean, in %.

(the largest chip's device op time in the traced window ÷ the mean over
the chips − 1) × 100: the paper's α-balance of the tile tasks, read on the
chips. A chip's device op time here is the sum of its op durations outside
the collectives and the control-flow ops (``bench.distributed_ops``): a
chip with less work waits for the others inside the collective that ends
the call, so with the collectives counted every chip reads the same time
(0.0013% on the four-chip cell's chips), and the wait shows in
``dist.collective_share`` instead. Nothing to read on one chip.
"""

from collections import defaultdict

from bench.distributed_ops import is_collective, is_control_flow


def read(ctx):
    red = ctx.reduced
    if red.devices < 2:
        return None
    per_chip = defaultdict(float)
    for o in red.ops:
        if not (is_control_flow(o) or is_collective(red, o)):
            per_chip[o.device] += o.end - o.start
    mean = sum(per_chip.values()) / red.devices
    if mean <= 0:
        return None
    return 100.0 * (max(per_chip.values()) / mean - 1.0)
