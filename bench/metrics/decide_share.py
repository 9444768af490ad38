"""Share of the window's wall time spent inside ``Policy.decide`` (a
benchmark-side span around every call), in percent."""


def read(ctx):
    wall = ctx.info.get("wall_s")
    if not wall or "decide_s" not in ctx.info:
        return None
    return 100.0 * ctx.info["decide_s"] / wall
