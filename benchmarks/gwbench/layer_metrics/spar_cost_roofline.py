"""The cost contraction's share of its roofline: the least time its
algorithmic work needs on this chip over the device time of the ops
attributed to it (``cost_kernels.json``).

Every dispatched lane, filler lanes included, runs one contraction per
outer PGA step and one for the final objective: outer_iters + 1 calls of
``peaks.cost_contraction_work`` at the problem's own sizes. Silent where
no attributed op ran."""
import peaks

UNIT = "%"
LAYER = "cost assembly"
MOVES = "solves_per_s"


def read(ctx):
    cost = ctx.attributed.get("spar_cost", {})
    if not ctx.lanes or not cost.get("events") or cost["seconds"] <= 0:
        return None
    solver = ctx.cell.config["solver"]
    n = ctx.cell.traffic["pool"]["n"]
    work = peaks.cost_contraction_work(solver["s_per_n"] * n, n, n,
                                       solver["loss"])
    least, _ = peaks.least_time_s(work, ctx.peaks)
    calls = ctx.lanes * (solver["outer_iters"] + 1)
    return 100.0 * calls * least / cost["seconds"]
