"""Seconds per restore spent reading the checkpoint file and decoding it
into the state (the program's spans ``ckpt.restore.read`` and
``ckpt.restore.decode``), self time summed over the traced window, over
its ``ckpt.restore`` spans."""
from harness.program_spans import per_span


def read(ctx):
    return per_span(("ckpt.restore.read", "ckpt.restore.decode"),
                    "ckpt.restore")
