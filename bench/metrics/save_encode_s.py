"""Seconds per checkpoint save spent serializing the gathered state into
one checkpoint image (the program's span ``ckpt.save.encode``:
``serialize_tree`` and ``to_bytes``), self time summed over the traced
window, over its ``ckpt.save`` spans."""
from harness.program_spans import per_span


def read(ctx):
    return per_span(("ckpt.save.encode",), "ckpt.save")
