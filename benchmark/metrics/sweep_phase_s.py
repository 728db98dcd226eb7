"""Seconds per check in the program's `elle.cycle-sweep` span: one cycle
sweep per projection, with the host syncs between them, and host
classification where a sweep finds a cycle."""

from statistics import fmean


def read(ctx):
    d = ctx.spans.get("elle.cycle-sweep")
    return fmean(d) if d else None
