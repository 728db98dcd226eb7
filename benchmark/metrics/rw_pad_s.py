"""Seconds per check in the program's `elle.pad` spans: the host pad of the
packed history and the upload of each padded column."""


def read(ctx):
    d = ctx.spans.get("elle.pad")
    return sum(d) / ctx.checks if d else None
