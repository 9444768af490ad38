"""The grouped expert products' share of their roofline, in percent: the
least time for the window's grouped products
(``harness.moe_counts.expert_gmm``: the routed rows held here per step
times the window's steps, and the held experts' weights once per layer
call) over the device time of the operations under the program's
``moe.experts`` scope (the row gather, the three products and the
activation, forward, recomputed and backward).  The driver names those
operations from the compiled step (``ctx.info["scoped_ops"]``)."""
from harness.counts import roofline_seconds
from harness.moe_counts import expert_gmm, moe_layers, rows_per_step


def read(ctx):
    names = set(ctx.info.get("scoped_ops", {}).get("moe.experts", ()))
    rows, steps = rows_per_step(), ctx.info.get("steps")
    if ctx.trace is None or not names or rows is None or not steps:
        return None
    busy = sum(e.dur_ns for e in ctx.trace.ops
               if e.name.split(" ", 1)[0].lstrip("%") in names)
    if not busy:
        return None
    ops, nbytes = expert_gmm(ctx.config, rows * steps,
                             moe_layers(ctx.config) * steps)
    return 100.0 * roofline_seconds(ops, nbytes, ctx.peak)[0] / (busy * 1e-9)
