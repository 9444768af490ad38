"""Share of the decide kernel's lanes that score a real job x site pair,
in percent: the program's counter ``decide.rows`` (unpadded pairs) over
``decide.lanes`` (pairs computed, padding of the batch, job and site
axes included), over the traced window's kernel calls."""
from harness.program_spans import counter_share


def read(ctx):
    return counter_share("decide.rows", "decide.lanes")
