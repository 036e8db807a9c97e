"""``kernel.syrk_roofline``: the Gram kernels' share of their roofline.

Over every ``syrk_*`` launch in the traced window: the least time the chip
could take for the launches' plain work (``bench.work``: flops over the
bf16 peak, or bytes over the HBM peak, whichever is larger) over the
device time they took, in %. Nothing to read where no such kernel ran.
"""

from bench.metrics_common import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, lambda k: k.startswith("syrk"))
