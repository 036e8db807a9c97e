"""``solve.factor_share``: device time in the Cholesky and substitutions, in %.

Ops under the program's ``solve.cholesky`` and ``solve.substitution``
scopes (``repro.obs`` spans, on in the traced run), together with every
``potrf`` and ``trsm_*`` launch, over the device time of all ops in the
traced window. Nothing to read where no op carries those names.
"""

_SCOPES = ("/solve.cholesky", "/solve.substitution")
_KERNELS = ("potrf", "trsm_n", "trsm_t")


def read(ctx):
    red = ctx.reduced
    total = red.seconds(red.ops)

    def factor(o):
        launch = ctx.launches.get(o.name)
        if launch is not None and launch.kernel in _KERNELS:
            return True
        return any(s in red.op_name(o) for s in _SCOPES)

    ops = [o for o in red.ops if factor(o)]
    if not ops or total <= 0:
        return None
    return 100.0 * red.seconds(ops) / total
