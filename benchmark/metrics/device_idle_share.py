"""Per cent of the traced window in which no operation ran on a chip,
averaged over the chips (each chip's share is logged apart)."""

from statistics import fmean


def read(ctx):
    busy = ctx.trace.busy_s()
    if not busy:
        return None
    return 100.0 * (1.0 - fmean(busy.values()) / ctx.trace.window_s)
