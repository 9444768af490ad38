"""Share of the checkpoint payload that moved between a leaf's own host
buffer and the file with no staging copy, in percent: the program's
counter ``ckpt.bytes_direct`` over ``ckpt.bytes``, both counted on every
save's write and every restore's read of the traced window."""
from harness.program_spans import counter_share


def read(ctx):
    return counter_share("ckpt.bytes_direct", "ckpt.bytes")
