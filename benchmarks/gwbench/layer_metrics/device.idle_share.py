"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device op intervals) / window, from the profiler trace."""
UNIT = "%"
LAYER = "device"
MOVES = "solves_per_s"


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
