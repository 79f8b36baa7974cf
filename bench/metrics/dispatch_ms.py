"""Mean time per super-round from ``slot_round``'s entry until its jitted
call returns (``quegel.dispatch``: batch stacking, masks, dispatch), from
the program's spans."""
from bench.harness import program


def read(run):
    return program.span_ms_per_round(run, "quegel.dispatch")
