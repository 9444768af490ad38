"""How unevenly routing loads the experts held here: the program's
counter ``moe.rows_max`` (each expert layer's largest held expert's
rows) over the mean rows of a held expert (``moe.rows_held`` over the
experts held), both summed over the steps read in the traced window.
1 is an even load."""
from harness import program_spans


def read(ctx):
    reg = program_spans.registry()
    if reg is None or not reg[1].get("moe.rows_held"):
        return None
    counters = reg[1]
    return counters.get("moe.rows_max", 0) / (
        counters["moe.rows_held"] / ctx.config["num_experts"])
