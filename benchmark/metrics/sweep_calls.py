"""Runs of the cycle-sweep program per check (`sweep.call` spans): one
per projection, more where a sweep is rerun with a larger budget."""


def read(ctx):
    d = ctx.spans.get("sweep.call")
    return len(d) / ctx.checks if d else None
