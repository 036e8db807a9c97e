"""``setup.lower_s``: seconds of set-up spent lowering the cell's program.

Lowering turns its jaxpr into a StableHLO module, the Pallas kernels'
Mosaic included. JAX's own ``lower`` event of the program, as
``repro.obs.compiles`` recorded it in this process: the program is the one
traced around the program's root span, so the operand maker compiled
before it and the reference compiled after the window are left out.
"""

from bench.program_records import setup_step


def read(ctx):
    return setup_step("lower")
