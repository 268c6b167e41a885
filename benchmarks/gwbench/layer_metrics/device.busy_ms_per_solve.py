"""Device time per solve: the union of the device op intervals in the
traced window over the solves completed in it. It covers the solver on
the device: outer PGA steps, inner Sinkhorn, cost assembly."""
UNIT = "ms"
LAYER = "solver on device"
MOVES = "solves_per_s"


def read(ctx):
    if not ctx.solves or ctx.busy_s <= 0:
        return None
    return 1e3 * ctx.busy_s / ctx.solves
