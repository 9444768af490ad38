"""Mean seconds of the benchmark-side span ``save`` over the
migration cycles of the traced window."""


def read(ctx):
    times = ctx.info.get("span_save")
    return sum(times) / len(times) if times else None
