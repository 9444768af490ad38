"""Simulator events (``SimResult.ticks``) per wall second of the
window: the event loop's own rate."""


def read(ctx):
    wall = ctx.info.get("wall_s")
    if not wall or "sim_events" not in ctx.info:
        return None
    return ctx.info["sim_events"] / wall
