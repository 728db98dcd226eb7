"""Seconds per check in the program's `rw.core-call` spans: dispatch and
device run of the fused rw program, to the host reads of its verdict bits
and overflow counts."""


def read(ctx):
    d = ctx.spans.get("rw.core-call")
    return sum(d) / ctx.checks if d else None
