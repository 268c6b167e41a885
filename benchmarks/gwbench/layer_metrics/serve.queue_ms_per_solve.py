"""Host wall time a request waits in its bucket: the program's
``serve.queue`` spans, each from the request's append to its bucket to
the start of the flush that took it, summed over the traced window, per
solve. Silent where the program records no such span."""
UNIT = "ms"
LAYER = "serve batching"
MOVES = "solves_per_s"


def read(ctx):
    waits = [r["duration_s"] for r in ctx.spans if r["name"] == "serve.queue"]
    if not ctx.solves or not waits:
        return None
    return 1e3 * sum(waits) / ctx.solves
