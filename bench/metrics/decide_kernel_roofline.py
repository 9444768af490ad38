"""The decide kernel's share of its roofline, in percent: the least
time the chip needs for the unpadded job x site rows of every kernel
call in the traced window (``harness.counts.decide_kernel``), over the
device time of the kernel's operations in the trace.  The kernel is the
Pallas ``_dest_kernel`` of ``repro.core.policy_kernels``; the trace
names it by its HLO text alone, so it is taken as the Pallas custom call
whose result is int32 destinations (the cell runs no other)."""
from harness.counts import roofline_seconds
from harness.trace import short_name

PALLAS = 'custom_call_target="tpu_custom_call"'


def is_decide_kernel(e) -> bool:
    return PALLAS in e.name and short_name(e.name).partition(" = ")[2].startswith("s32[")


def read(ctx):
    if ctx.trace is None or not ctx.info.get("decide_calls"):
        return None
    events = [e for e in ctx.trace.matching(PALLAS) if is_decide_kernel(e)]
    if not events:
        return None
    device_s = sum(e.dur_ns for e in events) * 1e-9
    least = sum(roofline_seconds(ops, nbytes, ctx.peak)[0]
                for call in ctx.info["decide_calls"] for ops, nbytes in call)
    return 100.0 * least / device_s
