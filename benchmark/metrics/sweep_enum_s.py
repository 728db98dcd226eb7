"""Seconds per check in the program's `sweep.enumerate` spans: the one
backward-edge enumeration of the edge-family union that every
projection's sweep then reads, up to the read of its backward count."""


def read(ctx):
    d = ctx.spans.get("sweep.enumerate")
    return sum(d) / ctx.checks if d else None
