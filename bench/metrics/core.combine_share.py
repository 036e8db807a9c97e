"""``core.combine_share``: device time in the recursion's sums, in %.

The ops outside any Pallas launch whose innermost program scope is one of
the recursion's operand sums and output combinations: ``strassen.encode``
(Winograd's ``s1…s4``, ``t1…t4`` and the quadrant slices),
``strassen.decode`` (the output combination and the quadrant assembly),
``ata.encode`` / ``ata.decode`` (the same, level-synchronous) and
``ata.slab_sum`` (the unrolled recursion's slab sums). Over the device
time of all ops in the traced window. Nothing to read where no op carries
those scopes.
"""

from bench.program_records import scope_share

_SCOPES = frozenset({"strassen.encode", "strassen.decode", "ata.encode",
                     "ata.decode", "ata.slab_sum"})


def read(ctx):
    return scope_share(ctx, _SCOPES)
