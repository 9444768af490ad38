"""Host milliseconds per ``Policy.decide`` call, from the program's own
spans: the ``decide`` span less the self time of its
``decide.put``/``.launch``/``.fetch`` children (gathering rows, building
and padding the batch, committing the answer), mean over the traced
window's calls.  The fleet driver's ``scored`` hook, which keeps each
kernel call's work counts and a copy of its answer for the check, runs
inside the call and outside those children, so its time is counted
here too."""
from harness.program_spans import decide_split


def read(ctx):
    got = decide_split()
    return None if got is None else got[0]
