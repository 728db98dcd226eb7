"""Device seconds per check in the Pallas forward-fill kernel
(`ops/pallas_fill._fill_kernel`, called through `_locf_pallas_padded`),
on the chip that spends most."""


def read(ctx):
    per = ctx.trace.op_s(lambda n: "fill_kernel" in n or "locf_pallas" in n)
    return max(per.values()) / ctx.checks if any(per.values()) else None
