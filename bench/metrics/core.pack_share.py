"""``core.pack_share``: device time in the root pad and the packing, in %.

The ops outside any Pallas launch whose innermost program scope is
``ata.pack`` (writing the result's blocks into packed storage) or
``ata.pad`` (the one root pad), over the device time of all ops in the
traced window. Nothing to read where no op carries those scopes.
"""

from bench.program_records import scope_share

_SCOPES = frozenset({"ata.pack", "ata.pad"})


def read(ctx):
    return scope_share(ctx, _SCOPES)
