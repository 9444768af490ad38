"""Share of the traced window in which no operation ran on the device,
in percent (1 - union of device operation intervals / window)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    return 100.0 * ctx.trace.idle_share
