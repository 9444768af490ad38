"""Milliseconds per ``Policy.decide`` call spent handing the decide
kernel to the device and waiting for its answer, from the program's own
spans: the self time of ``decide.put`` (operand copies),
``decide.launch`` (lookup and call of the compiled kernel) and
``decide.fetch`` (the blocking read of the destinations), mean over the
traced window's ``decide`` calls."""
from harness.program_spans import decide_split


def read(ctx):
    got = decide_split()
    return None if got is None else got[1]
