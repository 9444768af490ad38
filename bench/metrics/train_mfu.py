"""Model FLOP/s utilisation of training, in percent: forward plus
backward operations per token from the configuration's shapes
(``harness.counts.lm_train_flops_per_token``, no recomputation counted)
times the tokens of the traced window's steps, over its wall time and
the chip's bf16 peak.  The configuration is float32; at default
precision the TPU runs its float32 matrix products as bfloat16 passes,
so the bf16 peak is the one that applies."""
from harness.counts import lm_train_flops_per_token


def read(ctx):
    tokens, wall = ctx.info.get("tokens"), ctx.info.get("wall_s")
    if not tokens or not wall:
        return None
    flops = lm_train_flops_per_token(ctx.config, ctx.traffic["seq"]) * tokens
    return 100.0 * flops / (wall * ctx.peak["flops_bf16"])
