"""95th percentile, over every request completed in the window, of the
time from the client's ``submit`` call to its ``result()`` returning."""
import stats

UNIT = "ms"


def read(ctx):
    return 1e3 * stats.percentile(
        [d.finished - d.submitted for d in ctx.solved], 95)
