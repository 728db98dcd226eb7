"""Runs of the fused rw program per check (`rw.core-call` spans): 1, more
where a budget (rw_cap, max_k, max_rounds) regrows and the program reruns."""


def read(ctx):
    d = ctx.spans.get("rw.core-call")
    return len(d) / ctx.checks if d else None
