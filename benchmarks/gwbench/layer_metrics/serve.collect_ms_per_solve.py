"""Host time of the result path: the program's ``serve.collect`` spans
(the lane slice, the status and finiteness syncs, the value's copy to the
host, the bookkeeping), less the solo re-solves of their
``serve.fallback`` children, summed over the traced window, per solve.
Silent where the program records no such span."""
UNIT = "ms"
LAYER = "serve result"
MOVES = "solves_per_s"


def read(ctx):
    collect = [r for r in ctx.spans if r["name"] == "serve.collect"]
    if not ctx.solves or not collect:
        return None
    ids = {r["id"] for r in collect}
    fallback = sum(r["duration_s"] for r in ctx.spans
                   if r["name"] == "serve.fallback"
                   and r.get("parent_id") in ids)
    return 1e3 * (sum(r["duration_s"] for r in collect) - fallback) \
        / ctx.solves
