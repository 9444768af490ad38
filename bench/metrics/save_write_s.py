"""Seconds per checkpoint save spent writing the image to its file (the
program's span ``ckpt.save.write``), self time summed over the traced
window, over its ``ckpt.save`` spans."""
from harness.program_spans import per_span


def read(ctx):
    return per_span(("ckpt.save.write",), "ckpt.save")
