"""Mean seconds of the benchmark-side span ``migrate_job`` over the
migration cycles of the traced window."""


def read(ctx):
    times = ctx.info.get("span_migrate_job")
    return sum(times) / len(times) if times else None
