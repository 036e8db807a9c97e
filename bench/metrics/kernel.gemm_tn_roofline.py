"""``kernel.gemm_tn_roofline``: the product kernels' share of their roofline.

As ``kernel.syrk_roofline``, over every ``gemm_tn`` and ``gemm_tn_fused``
launch in the traced window.
"""

from bench.metrics_common import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, lambda k: k in ("gemm_tn", "gemm_tn_fused"))
