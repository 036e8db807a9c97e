"""Arithmetic that several per-layer readers share (``bench/metrics``)."""

from __future__ import annotations

from bench.work import launch_work, roofline_seconds

__all__ = ["kernel_roofline", "idle_share"]


def kernel_roofline(ctx, which):
    """Roofline share, in %, of the launches whose kernel name ``which``
    accepts: Σ roofline seconds of their plain work / Σ their device time.
    ``None`` where no such launch ran in the traced window."""
    red, least, took = ctx.reduced, 0.0, 0.0
    for o in red.ops:
        launch = ctx.launches.get(o.name)
        if launch is None or not which(launch.kernel):
            continue
        w = launch_work(launch)
        if w is None:
            continue
        least += roofline_seconds(w, ctx.peaks)[0]
        took += (o.end - o.start) * 1e-9
    return 100.0 * least / took if took > 0 else None


def idle_share(ctx):
    """1 − busy / window, in %; ``None`` with no window."""
    red = ctx.reduced
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
