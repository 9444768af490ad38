"""Seconds per checkpoint save spent copying the state from the device
to the host (the program's span ``ckpt.save.gather``), self time summed
over the traced window, over its ``ckpt.save`` spans."""
from harness.program_spans import per_span


def read(ctx):
    return per_span(("ckpt.save.gather",), "ckpt.save")
