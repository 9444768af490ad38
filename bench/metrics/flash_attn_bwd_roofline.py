"""The flash-attention backward kernels' share of their roofline, in
percent: for each call of the backward in the traced window, the least
time for a causal attention backward at the cell's batch, heads,
sequence and head size, over the device time of the backward's kernels.

A call is one ``flash_attention_bwd_dkv`` operation; the kernels are
every operation named ``flash_attention_bwd_*`` (the dk/dv and the dq
kernel of ``repro.kernels.flash_attention``).  An operation is selected
by its own name, not by an operand that reads a kernel's result, and the
forward's ``flash_attention_pallas`` is left to ``flash_attn_roofline``.
The least time is five matrix products over the pairs a causal mask
keeps (the scores again, dV, dP, dQ, dK: 2.5x the forward's two) and
q, k, v, out and dO read, dq, dk and dv written (2x the forward's four
tensors).  A design that recomputes the scores in both kernels does
more than this, so the share cannot pass 100 %.  It reads nothing where
no backward kernel ran."""
from harness.counts import causal_attention_fwd, roofline_seconds

CALL = "flash_attention_bwd_dkv"
KERNELS = "flash_attention_bwd_"


def _op_name(event) -> str:
    return event.name.split(" ", 1)[0].lstrip("%")


def read(ctx):
    if ctx.trace is None:
        return None
    kernels = [e for e in ctx.trace.ops if _op_name(e).startswith(KERNELS)]
    calls = [e for e in kernels if _op_name(e).startswith(CALL)]
    if not calls:
        return None
    c, t = ctx.config, ctx.traffic
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    ops, nbytes = causal_attention_fwd(t["batch"], c["num_attention_heads"],
                                       t["seq"], hd)
    least = roofline_seconds(2.5 * ops, 2 * nbytes, ctx.peak)[0] * len(calls)
    return 100.0 * least / (sum(e.dur_ns for e in kernels) * 1e-9)
