"""Device time of the cost contraction per solve: the ops that
``cost_kernels.json`` attributes to it (the Pallas kernels of
``kernels/spar_cost``), over the solves completed in the traced window.
Silent where no such op ran."""
UNIT = "ms"
LAYER = "cost assembly"
MOVES = "solves_per_s"


def read(ctx):
    cost = ctx.attributed.get("spar_cost", {})
    if not ctx.solves or not cost.get("events"):
        return None
    return 1e3 * cost["seconds"] / ctx.solves
