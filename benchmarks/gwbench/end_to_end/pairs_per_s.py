"""Pairs of an all-pairs job solved per second: the same count as
``solves_per_s`` (solves completed over the time from the window's start
to the last completion, the last batch finished), for the cells that fill
a distance matrix. Apart from ``solves_per_s`` so that each kind of cell
has a bound set from its own spread."""
UNIT = "pairs/s"


def read(ctx):
    return len(ctx.solved) / (ctx.t_end - ctx.t0)
