"""Seconds per restore spent building the state template a restoring
``Trainer`` initialises and then overwrites (the program's span
``train.restore_template``, around ``Trainer.init_state``), summed over
the traced window, over its ``ckpt.restore`` spans."""
from harness.program_spans import per_span


def read(ctx):
    return per_span(("train.restore_template",), "ckpt.restore")
