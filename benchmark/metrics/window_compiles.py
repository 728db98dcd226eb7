"""Backend compiles inside the measured window, counted from JAX's own
compile events.  A warm cell reads 0."""


def read(ctx):
    return ctx.counters["window_compiles"]
