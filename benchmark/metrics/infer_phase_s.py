"""Seconds per check in the program's `elle.infer` span: host pad,
staging and edge inference, up to the read of its anomaly counts."""

from statistics import fmean


def read(ctx):
    d = ctx.spans.get("elle.infer")
    return fmean(d) if d else None
