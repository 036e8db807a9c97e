"""``setup.trace_s``: seconds of set-up spent tracing the cell's program.

Tracing runs the program's Python: the unrolled recursion and every kernel
wrapper, down to a jaxpr. JAX's own ``trace`` event of the program, as
``repro.obs.compiles`` recorded it in this process: the program is the one
traced around the program's root span, so the operand maker compiled
before it and the reference compiled after the window are left out.
"""

from bench.program_records import setup_step


def read(ctx):
    return setup_step("trace")
