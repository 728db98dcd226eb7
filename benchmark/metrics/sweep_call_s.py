"""Seconds per check in the program's `sweep.call` spans: each run of
the cycle-sweep program, up to the read of its backward-edge count."""


def read(ctx):
    d = ctx.spans.get("sweep.call")
    return sum(d) / ctx.checks if d else None
