"""Process start to the end of warm-up: JAX and chip start-up, data from
the seed, every executable the traffic uses (from the compile cache after
a checkout's first run)."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
