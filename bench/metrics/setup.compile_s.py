"""``setup.compile_s``: seconds of set-up in the backend compile.

With a warm persistent cache that is hashing the module for the cache key
and loading the cached executable. JAX's own ``compile`` event of the
program, as ``repro.obs.compiles`` recorded it in this process: the
program is the one traced around the program's root span, so the operand
maker compiled before it and the reference compiled after the window are
left out.
"""

from bench.program_records import setup_step


def read(ctx):
    return setup_step("compile")
