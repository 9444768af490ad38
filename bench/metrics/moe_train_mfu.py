"""Model FLOP/s utilisation of training an expert-share hybrid, in
percent: forward plus backward operations (``harness.moe_counts``, 3x
the forward's matrix products at the configuration's shapes, no
recomputation counted) of the traced window's tokens, the expert
products counted from the routed rows the program computed per step,
over the window's wall time and the chip's bf16 peak (the float32
products run as bfloat16 passes at default precision, as for
``train_mfu``)."""
from harness.moe_counts import moe_train_flops, rows_per_step


def read(ctx):
    tokens, wall = ctx.info.get("tokens"), ctx.info.get("wall_s")
    rows = rows_per_step()
    if not tokens or not wall or rows is None:
        return None
    flops = moe_train_flops(ctx.config, ctx.traffic["seq"], tokens,
                            rows * ctx.info["steps"])
    return 100.0 * flops / (wall * ctx.peak["flops_bf16"])
