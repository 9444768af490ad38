"""The flash-attention forward kernel's share of its roofline, in
percent: for each of its calls in the traced window, the least time for
a causal forward at the cell's batch, heads, sequence and head size
(``harness.counts.causal_attention_fwd``), over the kernel's device
time.  The kernel is the Pallas ``_attn_kernel`` of
``repro.kernels.flash_attention``, which the trace names after its
jitted wrapper ``flash_attention_pallas``; its backward is XLA reference
math and not counted here."""
from harness.counts import causal_attention_fwd, roofline_seconds

NAMES = ("flash_attention_pallas",)


def read(ctx):
    if ctx.trace is None:
        return None
    events = ctx.trace.matching(*NAMES)
    if not events:
        return None
    c, t = ctx.config, ctx.traffic
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    ops, nbytes = causal_attention_fwd(t["batch"], c["num_attention_heads"],
                                       t["seq"], hd)
    least = roofline_seconds(ops, nbytes, ctx.peak)[0] * len(events)
    return 100.0 * least / (sum(e.dur_ns for e in events) * 1e-9)
