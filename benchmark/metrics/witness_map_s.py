"""Seconds per check in the program's `sweep.witness-map` spans: after
each sweep, the copies of its edge arrays to the host and the numpy
map of witness ids to edge positions."""


def read(ctx):
    d = ctx.spans.get("sweep.witness-map")
    return sum(d) / ctx.checks if d else None
