"""Share of the dispatched batch lanes that carry a real request, the
rest being filler lanes that round a flush up to a power of two; from the
server's own lane counters over the traced window."""
UNIT = "%"
LAYER = "serve batching"
MOVES = "solves_per_s"


def read(ctx):
    if not ctx.lanes:
        return None
    return 100.0 * (ctx.lanes - ctx.filler_lanes) / ctx.lanes
