"""Host time the server spends admitting one request: validation, content
hash, padding and signature. The self time of the program's
``serve.submit`` spans (less the batching they may run on a full bucket,
its ``serve.batch`` and ``serve.dispatch`` children), per solve."""
UNIT = "ms"
LAYER = "serve admission"
MOVES = "solves_per_s"

_BATCHING = ("serve.batch", "serve.dispatch")


def read(ctx):
    if not ctx.solves:
        return None
    submit = sum(r["duration_s"] for r in ctx.spans
                 if r["name"] == "serve.submit")
    nested = sum(r["duration_s"] for r in ctx.spans
                 if r["name"] in _BATCHING and r["parent"] == "serve.submit")
    return 1e3 * (submit - nested) / ctx.solves
