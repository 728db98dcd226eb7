"""Seconds per check in the program's `elle.infer.run` spans: dispatch
of edge inference and its device run, up to the read of its anomaly
counts."""


def read(ctx):
    d = ctx.spans.get("elle.infer.run")
    return sum(d) / ctx.checks if d else None
