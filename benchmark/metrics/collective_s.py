"""Device seconds per check in collectives (all-gather, all-reduce, ...),
on the chip that spends most.  Only a sharded check has any."""


def read(ctx):
    per = ctx.trace.collective_s()
    return max(per.values()) / ctx.checks if any(per.values()) else None
