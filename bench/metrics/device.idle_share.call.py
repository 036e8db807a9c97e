"""``device.idle_share.call``: the traced window's device idle share, in %.

1 − (union of device op intervals, averaged over the chips) / window.
"""

from bench.metrics_common import idle_share


def read(ctx):
    return idle_share(ctx)
