"""Mean seconds of the benchmark-side span ``restore`` over the
migration cycles of the traced window."""


def read(ctx):
    times = ctx.info.get("span_restore")
    return sum(times) / len(times) if times else None
