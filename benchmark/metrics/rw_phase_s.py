"""Seconds per check in the program's `elle.rw-core-check` spans: the whole
fused rw-register device check, the host pad and every run of the program."""


def read(ctx):
    d = ctx.spans.get("elle.rw-core-check")
    return sum(d) / ctx.checks if d else None
