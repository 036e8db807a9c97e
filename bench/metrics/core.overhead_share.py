"""``core.overhead_share``: device time outside the matrix products, in %.

The device time of ops that are neither a Pallas kernel nor an XLA dot or
convolution (nor a fusion of one): the recursion's operand sums, copies,
pads and the packed assembly. Over the device time of all ops in the
traced window.
"""


def read(ctx):
    red = ctx.reduced
    total = red.seconds(red.ops)
    if total <= 0:
        return None
    other = [o for o in red.ops
             if o.name not in ctx.launches and not red.is_mxu(o)]
    return 100.0 * red.seconds(other) / total
