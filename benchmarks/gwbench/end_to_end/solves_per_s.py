"""Solves completed over the time from the window's start to the last
completion. The loop finishes the batch in flight when the window closes,
so the window is a whole number of batches."""
UNIT = "solves/s"


def read(ctx):
    return len(ctx.solved) / (ctx.t_end - ctx.t0)
